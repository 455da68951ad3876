"""core.replication: ReplicationPlanner memory accounting and slice_mesh
shape/divisibility behaviour (multi-device shapes via a subprocess with
virtual host devices — the in-process device count is fixed at import)."""
import os
import subprocess
import sys

import pytest

from repro.configs import get_config
from repro.core.hardware import Hardware, H100_PAPER
from repro.core.replication import ReplicationPlanner, slice_mesh


@pytest.fixture(scope="module")
def cfg():
    return get_config("opt-1.3b")


def test_plan_memory_accounting(cfg):
    ctx, b = 331, 32
    planner = ReplicationPlanner(H100_PAPER, cfg, ctx=ctx)
    plan = planner.plan(b)
    model_b = cfg.num_params() * 2
    kv_b = cfg.kv_bytes_per_token(2) * ctx * b
    cap = H100_PAPER.hbm_bytes * 0.9
    assert plan.model_bytes == pytest.approx(model_b)
    assert plan.kv_bytes_per_replica == pytest.approx(kv_b)
    assert plan.capacity_bytes == pytest.approx(cap)
    assert plan.n_replicas == int(cap // (model_b + kv_b)) >= 1
    assert plan.total_bytes == pytest.approx(
        plan.n_replicas * (model_b + kv_b))
    assert plan.total_bytes <= plan.capacity_bytes
    assert plan.per_replica_batch == b
    assert "replicas" in plan.summary()


def test_plan_respects_max_replicas(cfg):
    plan = ReplicationPlanner(H100_PAPER, cfg, ctx=331).plan(
        8, max_replicas=2)
    assert plan.n_replicas == 2


def test_plan_never_below_one_replica(cfg):
    """Even when the model alone exceeds capacity the planner reports the
    degenerate 1-replica deployment rather than zero."""
    tiny = Hardware(name="tiny", peak_flops=1e12, hbm_bw=1e11,
                    link_bw=1e10, hbm_bytes=1e6)
    plan = ReplicationPlanner(tiny, cfg, ctx=331).plan(8)
    assert plan.n_replicas == 1
    assert plan.total_bytes > plan.capacity_bytes


def test_plan_reserve_fraction_shrinks_capacity(cfg):
    loose = ReplicationPlanner(H100_PAPER, cfg, ctx=331,
                               reserve_fraction=0.0).plan(32)
    tight = ReplicationPlanner(H100_PAPER, cfg, ctx=331,
                               reserve_fraction=0.5).plan(32)
    assert tight.capacity_bytes == pytest.approx(
        loose.capacity_bytes * 0.5)
    assert tight.n_replicas <= loose.n_replicas


def test_slice_mesh_identity_and_divisibility(mesh):
    subs = slice_mesh(mesh, 1)
    assert len(subs) == 1
    assert subs[0].axis_names == mesh.axis_names
    assert subs[0].shape == mesh.shape
    with pytest.raises(ValueError, match="not divisible"):
        slice_mesh(mesh, 2)       # data axis has size 1


_SLICE_SCRIPT = """
import numpy as np
from repro.launch.mesh import make_mesh
from repro.core.replication import slice_mesh

mesh = make_mesh((4, 1), ("data", "model"))
for r, per in ((2, 2), (4, 1)):
    subs = slice_mesh(mesh, r)
    assert len(subs) == r
    seen = set()
    for sub in subs:
        assert sub.axis_names == mesh.axis_names
        assert sub.shape["data"] == per and sub.shape["model"] == 1
        ids = {d.id for d in np.asarray(sub.devices).flat}
        assert not ids & seen          # disjoint slices
        seen |= ids
    assert seen == {d.id for d in np.asarray(mesh.devices).flat}
try:
    slice_mesh(mesh, 3)
except ValueError:
    pass
else:
    raise AssertionError("slice_mesh(4-dev, 3) should not divide")
print("OK")
"""


def test_slice_mesh_multi_device_shapes():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH="src" + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    res = subprocess.run([sys.executable, "-c", _SLICE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    assert "OK" in res.stdout
