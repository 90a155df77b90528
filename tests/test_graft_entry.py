"""Keep the driver entry points green.

Round 1's only red scoreboard light was `dryrun_multichip` failing in
the DRIVER'S environment.  These tests run both entry points the way
the driver does — a fresh subprocess, jax possibly pre-initialized with
the wrong device count — so a regression shows up here, not in the
round record.

The children are pinned to the CPU explicitly: a child that inherited
an unforced platform would claim the chip on a TPU host, and a chip
belongs to one process at a time.  Only ``XLA_FLAGS`` is stripped, so
the entry point still has to materialize its own virtual devices.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=540):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=timeout, env=env,
    )


def test_dryrun_multichip_8_from_fresh_process():
    r = _run(
        "import __graft_entry__ as g; g.dryrun_multichip(8); print('OK')"
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_dryrun_multichip_survives_preinitialized_jax():
    """The driver may have imported jax (and initialized a backend with
    too few devices) before calling; the device forcing must still
    work."""
    r = _run(
        "import jax; jax.devices(); "
        "import __graft_entry__ as g; g.dryrun_multichip(4); print('OK')"
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_entry_compiles_single_device():
    r = _run(
        "import os; os.environ['JAX_PLATFORMS'] = 'cpu'; "
        "import jax; jax.config.update('jax_platforms', 'cpu'); "
        "import __graft_entry__ as g; fn, args = g.entry(); "
        "out = jax.jit(fn)(*args); jax.block_until_ready(out); "
        "print('OK', out.shape)"
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
