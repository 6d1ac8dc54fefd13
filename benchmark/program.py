"""Everything the benchmark takes from the program: the system under test
(``cmd.convert.main``, called in-process exactly as the CLI does), its
counters, its program caches, where it keeps its compile cache, its native
build, and the parsers the comparison reads the program's artifacts with.
No other file of the benchmark imports ``nydus_snapshotter_tpu``.
"""

from __future__ import annotations

import contextlib
import io
import json
import sys

STAGES = ("layout", "h2d", "pass1_gear", "host_resolve", "pass2_digest")
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class VerbFailed(RuntimeError):
    pass


def cli(argv: list[str]) -> dict:
    """cmd.convert.main(argv); its one JSON line is captured and returned."""
    from nydus_snapshotter_tpu.cmd.convert import main as convert_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = convert_main(argv)
    if rc != 0:
        raise VerbFailed(f"cmd.convert exited {rc}: {argv}")
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def counters() -> dict:
    from nydus_snapshotter_tpu.ops import fused_convert

    disp, by_bytes, stages, fallbacks = fused_convert._counters()
    out = {"dispatches": int(disp.value()), "bytes": int(by_bytes.value()),
           "host_fallbacks": int(fallbacks.value())}
    for stage in STAGES:
        out[stage] = float(stages.value(stage))
    return out


def lane_programs() -> int:
    from nydus_snapshotter_tpu.ops import fused_convert

    return fused_convert._pass1._cache_size() + fused_convert._pass2._cache_size()


def prepare() -> str:
    """Native engine built from the checkout's sources if it is not there,
    compile cache where the program puts it -> the cache's directory."""
    from nydus_snapshotter_tpu.ops import native_cdc
    from nydus_snapshotter_tpu.utils import jax_cache

    with contextlib.redirect_stdout(sys.stderr):
        if not native_cdc.available():
            raise RuntimeError("the native engine did not build or load")
    return jax_cache.enable()


def count_cache_misses() -> list[int]:
    """-> a one-cell counter of persistent-cache misses from now on."""
    from jax import monitoring

    misses = [0]

    def on_event(event: str, **_kw) -> None:
        if event == CACHE_MISS_EVENT:
            misses[0] += 1

    monitoring.register_event_listener(on_event)
    return misses


def layer_bootstrap(layer_blob: bytes):
    from nydus_snapshotter_tpu.converter.convert import bootstrap_from_layer_blob

    return bootstrap_from_layer_blob(layer_blob)


def layer_blob_data(layer_blob: bytes) -> bytes:
    from nydus_snapshotter_tpu.converter.convert import blob_data_from_layer_blob

    return blob_data_from_layer_blob(layer_blob)
