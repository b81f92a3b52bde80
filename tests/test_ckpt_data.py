"""Checkpointer (atomicity, restore, gc) + data pipeline (determinism)."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import Checkpointer
from repro.configs import ShapeConfig, get_arch
from repro.data import PrefetchPipeline, SyntheticSource
from repro.launch.mesh import make_mesh


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (8, 4)),
                       "b": jnp.zeros((4,))},
            "opt": {"step": jnp.asarray(7, jnp.int32)}}


def test_save_restore_roundtrip(tmp_path):
    ck = Checkpointer(tmp_path)
    state = _state()
    ck.save(12, state, meta={"arch": "t"}, blocking=True)
    restored, manifest = ck.restore()
    assert manifest["step"] == 12
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                               np.asarray(state["params"]["w"]))
    assert int(restored["opt"]["step"]) == 7


def test_latest_and_gc(tmp_path):
    ck = Checkpointer(tmp_path, keep=2)
    for s in (10, 20, 30, 40):
        ck.save(s, _state(s), blocking=True)
    assert ck.latest_step() == 40
    kept = sorted(p.name for p in tmp_path.glob("step_*"))
    assert kept == ["step_00000030", "step_00000040"]
    assert ck.validate(40)


def test_atomicity_no_partial_visible(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(5, _state(), blocking=True)
    # a stale .tmp dir from a crashed writer must not be picked up
    (tmp_path / "step_00000099.tmp").mkdir()
    assert ck.latest_step() == 5


def test_restore_with_shardings(tmp_path):
    mesh = make_mesh((1,), ("data",))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    ck = Checkpointer(tmp_path)
    ck.save(1, _state(), blocking=True)
    restored, _ = ck.restore(shardings={"params": {"w": sh, "b": sh},
                                        "opt": {"step": sh}})
    assert restored["params"]["w"].sharding == sh


# ---------------- data pipeline ----------------

def test_source_deterministic_per_step():
    arch = get_arch("qwen3-8b").reduced()
    shape = ShapeConfig("t", "train", 32, 4)
    s1 = SyntheticSource(arch, shape, seed=3)
    s2 = SyntheticSource(arch, shape, seed=3)
    b1, b2 = s1.batch_at(17), s2.batch_at(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = s1.batch_at(18)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_source_host_sharding_disjoint():
    arch = get_arch("qwen3-8b").reduced()
    shape = ShapeConfig("t", "train", 32, 8)
    a = SyntheticSource(arch, shape, host_id=0, n_hosts=2).batch_at(0)
    b = SyntheticSource(arch, shape, host_id=1, n_hosts=2).batch_at(0)
    assert a["tokens"].shape == (4, 32)
    assert not np.array_equal(a["tokens"], b["tokens"])


def test_prefetch_pipeline_order_and_restart():
    arch = get_arch("qwen3-8b").reduced()
    shape = ShapeConfig("t", "train", 16, 2)
    src = SyntheticSource(arch, shape, seed=1)
    pipe = PrefetchPipeline(src, prefetch_depth=3, start_step=5)
    got = []
    for step, batch in pipe:
        got.append((step, batch["tokens"].copy()))
        if len(got) == 4:
            break
    pipe.close()
    assert [g[0] for g in got] == [5, 6, 7, 8]
    # restart replay: same steps -> same bytes
    np.testing.assert_array_equal(got[2][1], src.batch_at(7)["tokens"])
