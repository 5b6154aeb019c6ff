"""Multi-device parity for the tensor-parallel attention backends.

Both tests fork `tests/_sharded_parity_child.py` under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the parent
process pins JAX to one CPU device (conftest), and the device-count flag
only takes effect before jax initializes, so the sharded paths can only
be exercised in a subprocess. The child asserts:

* ``ops``  — the raceit_fused_tp / raceit_gqa_tp backends produce
  *bitwise identical* decode outputs (contiguous per-row kv_len AND
  block-paged pool) vs the single-device serving chain, MHA + GQA x
  mesh model={1,2,4,8}, with prefill held to <= 4 ulp (XLA re-associates
  the f32 epilog inside shard_map); and that resolution picks the TP
  backends exactly when the mesh has a model axis > 1 that divides
  n_kv_heads.
* ``soak`` — end-to-end greedy tokens through `GenerationEngine`
  (params device_put under FSDP/TP specs) and generated mixed-length
  `ContinuousBatcher` paged traces on a 4-device mesh are identical to
  the no-mesh run, with the page-pool invariants held every step.

These are the CI ``distributed`` lane's teeth (ISSUE 10 acceptance).
"""
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
CHILD = ROOT / "tests" / "_sharded_parity_child.py"


def _run_child(mode, sentinel):
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "HOME": "/tmp", "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(CHILD), mode], env=env,
                         capture_output=True, text=True, timeout=900)
    assert sentinel in out.stdout, (
        f"child {mode!r} failed:\n--- stdout ---\n{out.stdout[-2000:]}\n"
        f"--- stderr ---\n{out.stderr[-4000:]}")


@pytest.mark.slow
def test_sharded_op_parity_8dev():
    """Bitwise TP decode parity, MHA+GQA x mesh {1,2,4,8}."""
    _run_child("ops", "PARITY_OK")


@pytest.mark.slow
def test_sharded_serving_soak_4dev():
    """Engine + paged continuous-batching token parity on a 4-way mesh."""
    _run_child("soak", "SOAK_OK")
