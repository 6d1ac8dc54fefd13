"""On-demand builder for the in-tree C++ targets (native/bin/*).

Build artifacts are git-ignored, so a fresh checkout has none; consumers
(ops/native_cdc.py for libchunk_engine.so, fanotify/server.py for
optimizer-server) call :func:`ensure_built` on first use. Discipline:

- build into a private temp dir and land via atomic ``os.replace`` so a
  concurrent process never dlopens/execs a half-written file;
- refuse nothing here — staleness policy is the caller's (native_cdc
  refuses a stale .so; a stale tracer binary is rebuilt below);
- remember build FAILURES on disk keyed on source mtimes, so other
  processes degrade instantly instead of each re-paying a doomed
  compile. The marker carries the compiler's stderr after the stamp
  line, so :func:`failure_reason` can tell callers WHY the library is
  unbuildable even when this process never ran the compile. Post-build
  filesystem errors leave no memo: the toolchain works, the next
  process should simply retry.
"""

from __future__ import annotations

import glob
import os
import shutil
import subprocess

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")

# Compiler stderr kept in the failure memo: enough for the first errors,
# bounded so a pathological template spew cannot bloat the marker.
_MEMO_STDERR_CAP = 8192


def src_stamp(src_subdir: str) -> str:
    """Newest source mtime under native/<src_subdir> ('' when unreadable)."""
    src_dir = os.path.join(_NATIVE_DIR, src_subdir)
    try:
        return str(
            max(os.path.getmtime(os.path.join(src_dir, f)) for f in os.listdir(src_dir))
        )
    except (OSError, ValueError):
        return ""


def target_path(target: str) -> str:
    return os.path.join(_NATIVE_DIR, "bin", target)


def _marker_path(target: str) -> str:
    return os.path.join(_NATIVE_DIR, "bin", f".build_failed.{target}")


def sources_newer(target: str, src_subdir: str) -> bool:
    stamp = src_stamp(src_subdir)
    try:
        return bool(stamp) and float(stamp) > os.path.getmtime(target_path(target))
    except OSError:
        return False


def failure_reason(target: str) -> str:
    """The memoized compiler error for ``target`` ('' when there is no
    failure memo). First line of the marker is the source stamp; the rest
    is the captured stderr of the failed compile — possibly from another
    process entirely, which is the point: repeat callers get the WHY
    without re-paying the doomed compile."""
    try:
        with open(_marker_path(target)) as fp:
            memo = fp.read()
    except OSError:
        return ""
    _stamp, _nl, stderr = memo.partition("\n")
    return stderr.strip()


def rebuild_from_sources(stderr) -> None:
    """Rebuild native/bin's default targets from the committed sources,
    trusting no artifact (``make -B``) and no failure memo found on disk.
    ``make``'s output goes to ``stderr``; raises CalledProcessError when
    the build fails."""
    for memo in glob.glob(_marker_path("*")):
        os.unlink(memo)
    subprocess.run(
        ["make", "-B", "-C", _NATIVE_DIR],
        stdout=stderr,
        stderr=stderr,
        check=True,
        timeout=600,
    )


def ensure_built(target: str, src_subdir: str) -> bool:
    """Build native/bin/<target> if missing or stale. True when the
    artifact is present and current afterwards."""
    path = target_path(target)
    if os.path.exists(path) and not sources_newer(target, src_subdir):
        return True
    marker = _marker_path(target)
    stamp = src_stamp(src_subdir)
    try:
        with open(marker) as fp:
            if fp.read().partition("\n")[0] == stamp:
                return False  # this exact source state already failed
    except OSError:
        pass
    if not shutil.which("make") or not shutil.which("g++"):
        return False
    tmp = f"bin.build.{target}.{os.getpid()}"
    try:
        stderr = ""
        try:
            proc = subprocess.run(
                ["make", "-C", _NATIVE_DIR, f"{tmp}/{target}", f"BIN_DIR={tmp}"],
                capture_output=True,
                timeout=120,
            )
            ok = proc.returncode == 0
            if not ok:
                stderr = proc.stderr.decode("utf-8", "replace")[:_MEMO_STDERR_CAP]
        except (OSError, subprocess.TimeoutExpired) as e:
            ok = False
            stderr = f"{type(e).__name__}: {e}"[:_MEMO_STDERR_CAP]
        if not ok:
            try:
                os.makedirs(os.path.dirname(marker), exist_ok=True)
                with open(marker, "w") as fp:
                    fp.write(stamp + "\n" + stderr)
            except OSError:
                pass
            return False
        os.makedirs(os.path.dirname(path), exist_ok=True)
        os.replace(os.path.join(_NATIVE_DIR, tmp, target), path)
        try:
            os.unlink(marker)
        except OSError:
            pass
        return True
    except OSError:
        return False
    finally:
        shutil.rmtree(os.path.join(_NATIVE_DIR, tmp), ignore_errors=True)
