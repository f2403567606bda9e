"""Distributed core (two-level shadow, blocked gram) + distribution layer
(sharding rules, lowering) on multi host-device meshes via subprocess."""
import os
import subprocess
import sys

import numpy as np

import jax

from repro.configs import get_config
from repro.launch import sharding as shd
from repro.models import api

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def _run_multidevice(code: str, n_dev: int = 8):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True)
    assert r.returncode == 0 and "OK" in r.stdout, \
        (r.stdout[-1000:], r.stderr[-3000:])


def test_two_level_shadow_and_blocked_gram_8dev():
    _run_multidevice("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import gaussian, shadow_rsde, gram_matrix
from repro.core.distributed import blocked_gram_rows, distributed_assign
from repro.core import mmd as M
from repro.data import make_dataset
x, y, sigma = make_dataset("pendigits", seed=1, n=1024)
ker = gaussian(sigma)
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
r1 = shadow_rsde(x, ker, 4.0)
r2 = shadow_rsde(x, ker, 4.0, mesh=mesh)
assert r2.scheme == "shadow-ingest"
assert r2.weights.sum() == 1024   # exact int32 masses
mmd2 = M.mmd_weighted(ker, x, r2.centers, r2.weights)
assert mmd2 <= ker.mmd_bound(2.0) + 1e-6   # ell/2 worst case (2-level)
assert mmd2 <= 2 * M.mmd_weighted(ker, x, r1.centers, r1.weights) + 0.05
g = blocked_gram_rows(x, r2.centers, ker, mesh)
g_ref = gram_matrix(ker, jnp.asarray(x), jnp.asarray(r2.centers))
np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-5)
a = distributed_assign(x, r2.centers, mesh)
d = np.linalg.norm(x - r2.centers[np.asarray(a)], axis=1)
assert (d < 2 * ker.epsilon(4.0) + 1e-4).all()
print("OK")
""")


def test_chunked_ingest_select_8dev():
    """Out-of-core sharded selection (core/ingest_pipeline.py): per-chunk
    rows shard over 8 devices, candidates merge weight-exactly on host —
    covering the uneven-last-shard and empty-local-shard regressions."""
    _run_multidevice("""
import numpy as np
from repro.launch.mesh import make_mesh
from repro.core.ingest_pipeline import pad_block, select_streaming

mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
cent = rng.uniform(0, 1, (20, 5))
x = (cent[rng.integers(0, 20, 2000)]
     + 0.05 * rng.normal(size=(2000, 5))).astype(np.float32)
eps, chunk = 0.2, 512  # 2000 % 512 != 0: ragged final chunk

def chunks():
    for s in range(0, 2000, chunk):
        blk = x[s : s + chunk]
        yield pad_block(blk, chunk)[0], blk.shape[0]

rsde, stats = select_streaming(chunks(), eps, block=32, mesh=mesh)
assert stats.chunks == 4 and stats.rows == 2000
assert rsde.weights.sum() == 2000.0, rsde.weights.sum()  # weight-exact
d = np.linalg.norm(x[:, None] - rsde.centers[None], axis=2).min(1)
assert (d < 2 * eps + 1e-5).all()                        # 2*eps cover
# empty-local-shard regression: 100 valid rows of a 512-row chunk leave
# six of the eight devices with ZERO valid rows (zero survivors each)
rsde2, st2 = select_streaming(
    iter([(pad_block(x[:100], chunk)[0], 100)]), eps, block=32, mesh=mesh)
assert st2.rows == 100 and rsde2.weights.sum() == 100.0
print("OK")
""")


def test_ingest_fit_8dev_equals_two_level_reference():
    """ingest_fit on an 8-device mesh: every chunk's rows split over the
    devices, level 1 per device, the candidate buckets all-gathered and
    folded on the device as one batch in device order.  The merged centers
    and masses equal the plain two-level reference over the same
    per-device split (blocked selection per shard, then the host merge of
    each chunk's candidates), and the fit equals the single-device fit of
    that reference set."""
    _run_multidevice("""
import sys
import numpy as np
sys.path.insert(0, @TESTS@)
from merge_reference import StreamingMerge
from repro.core import fit_centers, gaussian
from repro.core.ingest_pipeline import ingest_fit, pad_block
from repro.core.shadow import shadow_select_blocked
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(1)
cent = rng.uniform(0, 1, (24, 5))
n, chunk, block = 3000, 1024, 32
x = (cent[rng.integers(0, 24, n)]
     + 0.05 * rng.normal(size=(n, 5))).astype(np.float32)
ker = gaussian(0.6)
eps = ker.epsilon(3.0)

def chunks():
    for s in range(0, n, chunk):
        blk = x[s : s + chunk]
        yield pad_block(blk, chunk)[0], blk.shape[0]

model, stats = ingest_fit(chunks(), ker, 4, ell=3.0, block=block, mesh=mesh)
ref = StreamingMerge(5, eps, block=block)
loc = chunk // 8
for s in range(0, n, chunk):
    cs, ws = [np.zeros((0, 5), np.float32)], [np.zeros((0,))]
    for j in range(8):
        rows = x[s + j * loc : min(s + (j + 1) * loc, n)]
        if len(rows):
            c, w, _, m = shadow_select_blocked(rows, eps, block=block)
            cs.append(c[:m])
            ws.append(w[:m])
    ref.update(np.concatenate(cs), np.concatenate(ws))
assert stats.rows == n and stats.m == ref.m, (stats.m, ref.m)
np.testing.assert_array_equal(model.centers, ref.centers)
np.testing.assert_array_equal(model.weights, ref.weights)
assert model.weights.sum() == n
one = fit_centers(ref.centers, ref.weights, n, ker, 4)
np.testing.assert_allclose(model.eigvals, one.eigvals, rtol=1e-4)
print("OK")
""".replace("@TESTS@", repr(os.path.dirname(os.path.abspath(__file__)))))


def test_sharded_fit_compiles_once_per_capacity_bucket():
    """Two center sets whose m fall in one power-of-two bucket run the same
    compiled sharded fit."""
    from repro.core import gaussian
    from repro.core.distributed import _fit_rskpca_sharded, fit_rskpca_sharded
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    rng = np.random.default_rng(3)
    ker = gaussian(1.0)
    sizes = []
    for m in (150, 230):
        c = rng.normal(size=(m, 4)).astype(np.float32)
        w = rng.integers(1, 5, m).astype(np.float64)
        lam, proj = fit_rskpca_sharded(c, w, float(w.sum()), ker, 3, mesh,
                                       lobpcg_min_m=64)
        assert proj.shape == (m, 3) and np.isfinite(lam).all()
        sizes.append(_fit_rskpca_sharded._cache_size())
    assert sizes[1] == sizes[0]


def test_train_step_runs_on_2x2_mesh():
    """Numerically execute one sharded train step (not just lower) on a
    (data=2, model=2) host mesh — validates the full distribution stack."""
    _run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import api
from repro.launch import steps, sharding as shd
from jax.sharding import NamedSharding, PartitionSpec as P
cfg = get_config("mixtral_8x7b", smoke=True)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
shape = api.ShapeSpec("t", 32, 4, "train")
params_spec = api.param_specs(cfg)
p_sh = shd.param_shardings(params_spec, mesh, cfg)
opt_spec = steps.opt_specs(cfg, params_spec)
o_sh = shd.opt_shardings(opt_spec, params_spec, mesh, cfg)
batch = {k: jnp.asarray(v) for k, v in api.make_host_batch(cfg, shape).items()}
b_sh = shd.batch_shardings(
    {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}, mesh)
with mesh:
    params = jax.jit(lambda k: api.init_params(k, cfg), out_shardings=p_sh)(
        jax.random.PRNGKey(0))
    opt = jax.jit(lambda p: steps.init_opt(cfg, p), out_shardings=o_sh)(params)
    fn = jax.jit(steps.make_train_step(cfg, mesh, accum=2),
                 in_shardings=(p_sh, o_sh, b_sh, NamedSharding(mesh, P())),
                 out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
    losses = []
    for s in range(3):
        params, opt, metrics = fn(params, opt, batch, jnp.int32(s))
        losses.append(float(metrics["loss"]))
assert all(np.isfinite(l) for l in losses), losses
assert losses[-1] < losses[0], losses  # same batch 3x must overfit
print("OK")
""", n_dev=4)


def test_decode_step_runs_on_2x2_mesh():
    _run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import api
from repro.launch import steps, sharding as shd
cfg = get_config("gemma2_9b", smoke=True)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
shape = api.ShapeSpec("d", 32, 4, "decode")
lowered, _ = steps.lower_decode(cfg, shape, mesh)
compiled = lowered.compile()
params = api.init_params(jax.random.PRNGKey(0), cfg)
cache = api.init_cache(cfg, 4, 32)
with mesh:
    logits, cache2 = jax.jit(
        steps.make_decode_step(cfg, mesh))(params, cache,
                                           jnp.zeros((4, 1), jnp.int32),
                                           jnp.int32(0))
assert np.isfinite(np.asarray(logits)).all()
print("OK")
""", n_dev=4)


def test_param_rules_cover_every_leaf():
    """Every parameter leaf of every arch must match a sharding rule (no
    accidental replication of big tensors)."""
    import jax
    mesh_like = type("M", (), {})()
    for arch in ["qwen2_72b", "mixtral_8x7b", "jamba_52b", "rwkv6_1b6",
                 "whisper_base", "kimi_k2"]:
        cfg = get_config(arch, smoke=True)
        spec = api.param_specs(cfg)
        flat, _ = jax.tree_util.tree_flatten_with_path(spec)
        for path, leaf in flat:
            ps = shd._path_str(path)
            matched = any(__import__("re").search(pat, ps)
                          for pat, _ in shd._PARAM_RULES)
            big = np.prod(leaf.shape) > 4096
            assert matched or not big, (arch, ps, leaf.shape)
