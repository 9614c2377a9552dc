"""The driver-facing entry points must work with NO env help.

Round-1 regression: ``dryrun_multichip(8)`` crashed when jax came up on a
1-chip platform because ``__graft_entry__`` never forced the virtual CPU
mesh the way tests/conftest.py does.  These tests
invoke the entry points in a clean subprocess — empty of JAX_PLATFORMS /
XLA_FLAGS hints — exactly like the driver does.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Each case boots a CLEAN-env python (no JAX_PLATFORMS pin): on a chip host
# that process takes the chip, and the 8-virtual-device dryrun itself
# compiles a full multichip program. Up to 600 s per case does not fit the
# tier-1 (-m 'not slow') budget — these run in the driver-facing/on-chip
# lane instead.
pytestmark = pytest.mark.slow


def _clean_env():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env.pop("_PADDLE_TPU_DRYRUN_CHILD", None)
    return env


def test_dryrun_multichip_clean_subprocess():
    code = "import __graft_entry__ as g; g.dryrun_multichip(8)"
    subprocess.run([sys.executable, "-c", code], cwd=REPO, env=_clean_env(),
                   check=True, timeout=600)


def test_dryrun_multichip_after_jax_init():
    # Even if the caller already initialized jax on some platform, the
    # dryrun must still complete (subprocess fallback path).
    code = (
        "import jax; jax.devices(); "
        "import __graft_entry__ as g; g.dryrun_multichip(8)"
    )
    subprocess.run([sys.executable, "-c", code], cwd=REPO, env=_clean_env(),
                   check=True, timeout=600)


def test_entry_compiles():
    code = (
        "import jax, __graft_entry__ as g; "
        "fn, args = g.entry(); "
        "out = jax.jit(fn)(*args); jax.block_until_ready(out)"
    )
    subprocess.run([sys.executable, "-c", code], cwd=REPO, env=_clean_env(),
                   check=True, timeout=600)
