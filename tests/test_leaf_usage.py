"""What a recorded leaf's thread did inside it (trace.Stages): `cpu_s`,
`waits`, `preempts` and `gc_s`, read once a boundary from
getrusage(RUSAGE_THREAD) and a collector callback; nothing read, and no
callback installed, where nothing is recorded."""

import gc
import resource
import threading
import time

import pytest

from nydus_snapshotter_tpu import trace

USAGE = {"cpu_s", "waits", "preempts", "gc_s"}
pytestmark = pytest.mark.skipif(trace._RUSAGE_THREAD is None, reason="no RUSAGE_THREAD on this platform")


@pytest.fixture(autouse=True)
def fresh():
    trace.configure(enabled=True)
    yield
    trace.reset()


def spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def garbage_collected() -> None:
    for _ in range(2000):
        loop = []
        loop.append(loop)
    gc.collect()


def leaves(root: str = "root") -> dict:
    spans = trace.snapshot_spans()
    (top,) = [s for s in spans if s.name == root]
    return {s.name: s for s in spans if s.parent_id == top.span_id}


def run_leaves(root: str = "root") -> None:
    with trace.span(root), trace.Stages() as stages:
        stages.next("spin")
        spin(0.2)
        stages.next("sleep")
        time.sleep(0.1)
        stages.next("collect")
        garbage_collected()


def assert_worked(sp) -> None:
    """A spinning leaf's CPU is its wall, but for what the kernel or a wait took."""
    a = sp.attrs
    assert 0 < a["cpu_s"] <= sp.seconds + 0.005
    assert a["cpu_s"] >= 0.9 * sp.seconds or a["waits"] + a["preempts"] > 0, a


def assert_slept(sp) -> None:
    a = sp.attrs
    assert 0 <= a["cpu_s"] < 0.3 * sp.seconds and a["waits"] >= 1, a


def test_a_leaf_reads_what_its_thread_did():
    run_leaves()
    got = leaves()
    assert all(set(s.attrs) == USAGE for s in got.values())
    assert_worked(got["spin"])
    assert_slept(got["sleep"])
    assert got["collect"].attrs["gc_s"] > 0
    assert got["spin"].attrs["gc_s"] == got["sleep"].attrs["gc_s"] == 0


def test_two_threads_each_read_their_own():
    """Thread A spins and collects while thread B sleeps beside it: the
    process's CPU and collections are A's, and B reads none of them."""
    barrier = threading.Barrier(2)

    def a():
        barrier.wait()
        with trace.span("a"), trace.Stages() as stages:
            stages.next("work")
            spin(0.2)
            garbage_collected()

    def b():
        barrier.wait()
        with trace.span("b"), trace.Stages() as stages:
            stages.next("wait")
            time.sleep(0.25)

    threads = [threading.Thread(target=f) for f in (a, b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    work, wait = leaves("a")["work"], leaves("b")["wait"]
    assert work.thread != wait.thread
    assert_worked(work)
    assert work.attrs["gc_s"] > 0
    assert_slept(wait)
    assert wait.attrs["gc_s"] == 0


def test_a_leaf_closed_on_another_thread_reads_nothing(monkeypatch):
    """Its usage would be two threads' difference: it carries none. (A span
    cannot leave the thread it entered on, whose context it reset; the
    thread id is what the boundary compares, so it stands in here.)"""
    with trace.span("root"), trace.Stages() as stages:
        stages.next("crosses")
        monkeypatch.setattr(threading, "get_ident", lambda: -1)
        stages.next("after")
    got = leaves()
    assert not USAGE & set(got["crosses"].attrs)
    assert set(got["after"].attrs) == USAGE  # opened and closed under the one id


def test_one_reading_a_boundary(monkeypatch):
    """Three leaves in a row: four readings, the middle two shared."""
    calls = []
    real = resource.getrusage

    def counted(who):
        calls.append(who)
        return real(who)

    monkeypatch.setattr(resource, "getrusage", counted)
    with trace.span("root"), trace.Stages() as stages:
        for name in ("a", "b", "c"):
            stages.next(name)
    assert calls == [resource.RUSAGE_THREAD] * 4
    assert all(set(s.attrs) == USAGE for s in leaves().values())


def refuse(*args):
    raise AssertionError("getrusage was called")


@pytest.mark.parametrize("how", ["off", "sampled out"])
def test_nothing_is_read_where_nothing_is_recorded(monkeypatch, how):
    if how == "off":
        trace.configure(enabled=False)
    else:
        trace.configure(enabled=True, sample_ratio=0.0)
    assert trace._collector_clock not in gc.callbacks
    monkeypatch.setattr(resource, "getrusage", refuse)
    with trace.span("root"), trace.Stages() as stages:
        first = stages.next("a")
        spin(0.01)
        stages.next("b")
        garbage_collected()
    assert trace.snapshot_spans() == []
    assert stages.seconds["a"] == first.seconds > 0  # the stopwatch still times the stage


def test_the_collector_clock_follows_the_tracer(monkeypatch):
    monkeypatch.delenv("NTPU_TRACE", raising=False)
    monkeypatch.delenv("NTPU_TRACE_SAMPLE_RATIO", raising=False)
    assert gc.callbacks.count(trace._collector_clock) == 1
    trace.configure(enabled=True)
    assert gc.callbacks.count(trace._collector_clock) == 1
    trace.configure(enabled=False)
    assert trace._collector_clock not in gc.callbacks
    trace.reset()
    assert trace._collector_clock not in gc.callbacks
    assert trace.enabled()  # resolved from the environment: on by default
    assert gc.callbacks.count(trace._collector_clock) == 1


def test_a_plain_span_reads_nothing(monkeypatch):
    monkeypatch.setattr(resource, "getrusage", refuse)
    with trace.span("op", key="k"):
        with trace.span("child"):
            spin(0.01)
    assert {s.name: s.attrs for s in trace.snapshot_spans()} == {"op": {"key": "k"}, "child": {}}


def test_leaf_is_a_stage_on_its_own():
    with trace.span("root"):
        with trace.leaf("one", n=1) as sp:
            time.sleep(0.02)
            sp.annotate(m=2)
    got = leaves()["one"]
    assert set(got.attrs) == {"n", "m", *USAGE} and got.attrs["waits"] >= 1
