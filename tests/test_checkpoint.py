"""Checkpoint store: roundtrip, atomic publish, async, elastic restore."""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import (save_checkpoint, restore_checkpoint,
                              latest_step, AsyncCheckpointer)


def _tree():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                       "b": jnp.ones(3, jnp.bfloat16)},
            "step": jnp.asarray(7, jnp.int32)}


def test_roundtrip(tmp_path):
    d = str(tmp_path)
    tree = _tree()
    save_checkpoint(d, 7, tree)
    assert latest_step(d) == 7
    restored, step = restore_checkpoint(d, jax.eval_shape(lambda: tree))
    assert step == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_latest_points_to_newest_and_resume_picks_it(tmp_path):
    d = str(tmp_path)
    tree = _tree()
    save_checkpoint(d, 5, tree)
    tree2 = jax.tree.map(lambda x: x + 1, tree)
    save_checkpoint(d, 10, tree2)
    restored, step = restore_checkpoint(d, jax.eval_shape(lambda: tree))
    assert step == 10
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(tree2["params"]["w"]))


def test_no_torn_checkpoint_on_partial_write(tmp_path):
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree())
    # simulate a crashed half-written step dir: tmp dir left behind
    os.makedirs(os.path.join(d, "step_00000002.tmp"))
    assert latest_step(d) == 1  # LATEST still points at the published one


def test_async_checkpointer(tmp_path):
    d = str(tmp_path)
    ck = AsyncCheckpointer(d)
    ck.save(3, _tree())
    ck.wait()
    assert latest_step(d) == 3


def test_elastic_restore_to_different_device_count(tmp_path):
    """Save on 4 host devices, restore on 2 — the elastic-restart path."""
    d = str(tmp_path)
    script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=@N@"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import save_checkpoint, restore_checkpoint
from repro.launch.mesh import make_mesh
mesh = make_mesh((@N@,), ("data",))
sh = NamedSharding(mesh, P("data", None))
w = jax.device_put(jnp.arange(64.0).reshape(8, 8), sh)
if @SAVE@:
    save_checkpoint(@DIR@, 1, {"w": w})
else:
    spec = jax.eval_shape(lambda: jnp.zeros((8, 8)))
    tree, step = restore_checkpoint(@DIR@, {"w": spec},
                                    shardings={"w": sh})
    assert step == 1
    np.testing.assert_array_equal(np.asarray(tree["w"]),
                                  np.arange(64.0).reshape(8, 8))
    assert len(tree["w"].addressable_shards) == @N@
print("OK")
"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    for n, save in ((4, 1), (2, 0)):
        code = (script.replace("@N@", str(n)).replace("@SAVE@", str(save))
                .replace("@DIR@", repr(d)))
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True)
        assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]


def test_available_steps_skips_tmp_and_orders(tmp_path):
    from repro.checkpoint.store import available_steps
    d = str(tmp_path)
    assert available_steps(d) == []
    save_checkpoint(d, 4, _tree())
    save_checkpoint(d, 2, _tree())
    os.makedirs(os.path.join(d, "step_00000009.tmp"))
    assert available_steps(d) == [2, 4]


def test_crc_catches_corruption_and_fallback_restores(tmp_path):
    import pytest
    from repro.checkpoint.store import CheckpointCorrupt, available_steps
    d = str(tmp_path)
    save_checkpoint(d, 1, _tree())
    save_checkpoint(d, 2, _tree())
    shard = os.path.join(d, "step_00000002", "shard_0.npz")
    raw = bytearray(open(shard, "rb").read())
    raw[len(raw) // 3] ^= 0x40
    open(shard, "wb").write(bytes(raw))
    with pytest.raises(CheckpointCorrupt):
        restore_checkpoint(d, jax.eval_shape(lambda: _tree()))
    # the resumer contract: walk available_steps newest-first past the rot
    steps = [s for s in available_steps(d)]
    restored, step = restore_checkpoint(d, jax.eval_shape(lambda: _tree()),
                                        step=steps[-2])
    assert step == 1


def test_chaos_corrupt_site_is_caught_on_restore(tmp_path):
    import pytest
    from repro.checkpoint.store import CheckpointCorrupt
    from repro.runtime import chaos
    from repro.runtime.chaos import FaultPlan, FaultSpec
    d = str(tmp_path)
    with chaos.active(FaultPlan({"checkpoint.shard":
                                 FaultSpec(kind="corrupt", every=1)})):
        save_checkpoint(d, 3, _tree())
    with pytest.raises(CheckpointCorrupt):
        restore_checkpoint(d, jax.eval_shape(lambda: _tree()))


def test_numpy_template_restores_numpy_with_f64_intact(tmp_path):
    """The ingest-state contract: a float64 leaf saved and restored against
    a NUMPY template keeps float64 (jnp.asarray would silently round to f32
    with x64 off)."""
    d = str(tmp_path)
    tree = {"w": np.array([1.0, 2.0 + 2**-40], np.float64),
            "c": np.arange(6, dtype=np.float32).reshape(3, 2)}
    save_checkpoint(d, 1, tree)
    restored, _ = restore_checkpoint(
        d, {"w": np.zeros((0,), np.float64), "c": np.zeros((0, 2),
                                                           np.float32)})
    assert isinstance(restored["w"], np.ndarray)
    assert restored["w"].dtype == np.float64
    np.testing.assert_array_equal(restored["w"], tree["w"])  # bit-exact


def test_save_racing_interpreter_exit_publishes_atomically(tmp_path):
    """Satellite (b): an async save STILL in flight when the interpreter
    exits must complete its atomic publish (the atexit hook joins it before
    daemon threads are reaped) — never a step_<N>.tmp as the final state."""
    d = str(tmp_path)
    script = f"""
import numpy as np
from repro.checkpoint import AsyncCheckpointer
ck = AsyncCheckpointer({str(d)!r})
ck.save(5, {{"w": np.arange(4096.0)}})
# exit IMMEDIATELY: no wait(), the save races interpreter teardown
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert latest_step(d) == 5
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]
    restored, _ = restore_checkpoint(d, {"w": np.zeros((0,))})
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(4096.0))
