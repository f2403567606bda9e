"""Sharded fit/transform path (DESIGN.md §5): single-device-mesh parity
in-process, multi-device parity via an 8-host-device subprocess (the same
harness pattern as tests/test_distributed.py)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import gaussian, shadow_rsde, fit_rskpca, fit
from repro.core import distributed as dist
from repro.launch.mesh import data_mesh
from repro.data import make_dataset

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


@pytest.fixture(scope="module")
def fitted():
    x, _, sigma = make_dataset("german", seed=0, n=400)
    ker = gaussian(sigma)
    rsde = shadow_rsde(x, ker, 4.0)
    return x, ker, rsde


def test_sharded_fit_matches_single_device_on_1dev_mesh(fitted):
    x, ker, rsde = fitted
    mesh = data_mesh(1)
    m0 = fit_rskpca(rsde, ker, 5)
    m1 = fit_rskpca(rsde, ker, 5, mesh=mesh)
    np.testing.assert_allclose(m1.eigvals, m0.eigvals, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(m1.projector, m0.projector, atol=1e-5)
    z0 = m0.transform(x[:100])
    z1 = m1.transform(x[:100], mesh=mesh)
    np.testing.assert_allclose(z1, z0, atol=1e-5)


def test_sharded_lobpcg_matvec_path(fitted):
    """Force the row-distributed LOBPCG eigensolve at small m (the shard_map
    matvec inside the iteration) and check it recovers the eigh spectrum."""
    x, ker, rsde = fitted
    mesh = data_mesh(1)
    m0 = fit_rskpca(rsde, ker, 5)
    lam, proj = dist.fit_rskpca_sharded(
        rsde.centers, rsde.weights, rsde.n, ker, 5, mesh, lobpcg_min_m=8)
    np.testing.assert_allclose(np.asarray(lam), m0.eigvals, rtol=1e-3)
    assert proj.shape == m0.projector.shape
    assert np.isfinite(np.asarray(proj)).all()


def test_sharded_shadow_assign_matches_ops(fitted):
    from repro.kernels import ops
    x, ker, rsde = fitted
    mesh = data_mesh(1)
    idx, d2 = dist.sharded_shadow_assign(x[:333], rsde.centers, mesh)
    idx_r, d2_r = ops.shadow_assign(x[:333], rsde.centers)
    assert (np.asarray(idx) == np.asarray(idx_r)).all()
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2_r), atol=1e-4)


def test_sharded_serving_compiles_per_bucket(fitted):
    """Mesh serving must re-trace per shape BUCKET, not per query size:
    two ragged queries inside one (ndev*128) bucket share a compile."""
    x, ker, rsde = fitted
    mesh = data_mesh(1)
    mdl = fit_rskpca(rsde, ker, 5, mesh=mesh)
    before = dist._sharded_project_jit._cache_size()
    z1 = mdl.transform(x[:130], mesh=mesh)  # pads to the 256-row bucket
    mid = dist._sharded_project_jit._cache_size()
    z2 = mdl.transform(x[:200], mesh=mesh)  # same bucket: no new trace
    after = dist._sharded_project_jit._cache_size()
    assert mid - before == 1 and after == mid, (before, mid, after)
    assert z1.shape == (130, 5) and z2.shape == (200, 5)


def test_mesh_rejected_for_single_device_baselines(fitted):
    x, ker, _ = fitted
    with pytest.raises(ValueError, match="single-device"):
        fit(x, ker, 4, method="kpca", mesh=data_mesh(1))
    with pytest.raises(ValueError, match="single-device"):
        fit(x, ker, 4, method="uniform", m=40, mesh=data_mesh(1))


def test_front_door_mesh_produces_usable_model(fitted):
    x, ker, _ = fitted
    mesh = data_mesh(1)
    mdl = fit(x, ker, 4, method="shadow", ell=4.0, mesh=mesh)
    z = mdl.transform(x[:10], mesh=mesh)
    assert z.shape == (10, 4) and np.isfinite(z).all()
    # bf16 composes with the sharded path
    mdl16 = fit(x, ker, 4, method="shadow", ell=4.0, mesh=mesh,
                precision="bf16")
    assert mdl16.kernel.precision == "bf16"
    assert np.isfinite(mdl16.transform(x[:10], mesh=mesh)).all()


def test_sharded_fit_transform_8dev_matches_single():
    """Acceptance: sharded results match single-device to 1e-5 on a real
    multi-device (host) mesh, with only the (m, r) projector replicated."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", """
import numpy as np
from repro.core import gaussian, shadow_rsde, fit_rskpca
from repro.core import distributed as dist
from repro.launch.mesh import smoke_mesh
from repro.data import make_dataset

x, _, sigma = make_dataset("pendigits", seed=1, n=1024)
ker = gaussian(sigma)
rsde = shadow_rsde(x, ker, 4.0)
mesh = smoke_mesh()
assert len(mesh.devices.flat) == 8
m0 = fit_rskpca(rsde, ker, 5)
m1 = fit_rskpca(rsde, ker, 5, mesh=mesh)
np.testing.assert_allclose(m1.eigvals, m0.eigvals, atol=1e-5, rtol=1e-5)
np.testing.assert_allclose(m1.projector, m0.projector, atol=1e-5)
z0 = m0.transform(x[:333])
z1 = m1.transform(x[:333], mesh=mesh)
np.testing.assert_allclose(z1, z0, atol=1e-5)
# forced distributed-LOBPCG eigensolve agrees with eigh
lam, _ = dist.fit_rskpca_sharded(rsde.centers, rsde.weights, rsde.n,
                                 ker, 5, mesh, lobpcg_min_m=8)
np.testing.assert_allclose(np.asarray(lam), m0.eigvals, rtol=1e-3)
# row-sharded assign agrees with the single-device kernel
from repro.kernels import ops
idx, d2 = dist.sharded_shadow_assign(x[:999], rsde.centers, mesh)
i0, d0 = ops.shadow_assign(x[:999], rsde.centers)
assert (np.asarray(idx) == np.asarray(i0)).all()
np.testing.assert_allclose(np.asarray(d2), np.asarray(d0), atol=1e-4)
# n NOT divisible by the axis: padding rows must carry no weight and the
# front door must work end-to-end (data_mesh's 'always safe' contract)
from repro.core import fit
mdl = fit(x[:1001], ker, 4, method="shadow", ell=4.0, mesh=mesh)
r = shadow_rsde(x[:1001], ker, 4.0, mesh=mesh)
assert r.weights.sum() == 1001, r.weights.sum()
assert np.isfinite(mdl.transform(x[:77], mesh=mesh)).all()
print("OK")
"""], env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "OK" in r.stdout, \
        (r.stdout[-1000:], r.stderr[-3000:])
