"""Driver entry points stay runnable (__graft_entry__).

dryrun_multichip needs a fresh process (XLA_FLAGS must be set before the
backend initializes), so it runs as a subprocess — exactly how the driver
invokes it.
"""

import os
import subprocess
import sys

from nydus_snapshotter_tpu.utils import jax_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_8():
    env = jax_cache.child_env()
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=REPO,
        env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "overflowed the" in out.stdout  # the forced-overflow phase ran
    assert "dryrun_multichip OK" in out.stdout


def test_entry_compiles_single_device():
    env = jax_cache.child_env()
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    child = (
        "import jax; jax.config.update('jax_platforms', 'cpu');\n"
        "import __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "jax.block_until_ready(out)\n"
        "print('entry OK', [tuple(o.shape) for o in out])\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", child],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=REPO,
        env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "entry OK" in out.stdout
