"""Checkpoint round-trip under sharding: a partitioned TrainState saves
(sharded or pre-gathered), the sha256 integrity manifest stays valid,
and the same directory restores into a DIFFERENT partitioner's layout
(the template's shardings drive the restore)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.checkpoint import CheckpointManager
from sparkdl_tpu.partition import (
    DataParallelPartitioner,
    GENERIC_RULES,
    SPMDPartitioner,
    make_mesh,
)

rng = np.random.default_rng(11)


def _params():
    return {
        "dense": {"kernel": jnp.asarray(
            rng.standard_normal((16, 8)), jnp.float32),
            "bias": jnp.zeros((8,), jnp.float32)},
    }


def _state(part, params):
    tx = optax.adamw(1e-3)
    return {
        "params": part.shard_params(params),
        "opt_state": part.shard_opt_state(tx.init(params)),
        "step": part.shard_replicated(jnp.zeros((), jnp.int32)),
    }


def test_sharded_state_saves_with_valid_manifest(tmp_path):
    part = DataParallelPartitioner(make_mesh(dp=4, fsdp=2),
                                   zero_axis="fsdp")
    state = _state(part, _params())
    with CheckpointManager(str(tmp_path)) as mgr:
        assert mgr.save(1, state)
        mgr.wait()
        # PR 5 integrity manifest must cover the sharded save
        assert mgr.verify(1) is True


def test_restore_across_partitioners(tmp_path):
    """fsdp-sharded save -> restore replicated AND restore rule-sharded:
    the template decides the landing layout, values are identical."""
    params = _params()
    zero = DataParallelPartitioner(make_mesh(dp=4, fsdp=2),
                                   zero_axis="fsdp")
    state = _state(zero, params)
    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(3, state)
        mgr.wait()
        assert mgr.verify(3) is True

        # replicated template (plain dp partitioner)
        dp = DataParallelPartitioner(make_mesh(dp=8))
        got = mgr.restore(template=_state(dp, params))
        k = got["params"]["dense"]["kernel"]
        assert k.sharding.is_fully_replicated
        np.testing.assert_array_equal(
            np.asarray(k), np.asarray(params["dense"]["kernel"]))

        # rule-sharded template (SPMD partitioner, fsdp on the kernel)
        spmd = SPMDPartitioner(make_mesh(dp=1, fsdp=8), GENERIC_RULES)
        got2 = mgr.restore(template=_state(spmd, params))
        k2 = got2["params"]["dense"]["kernel"]
        assert not k2.sharding.is_fully_replicated
        np.testing.assert_array_equal(
            np.asarray(k2), np.asarray(params["dense"]["kernel"]))
        mu = got2["opt_state"][0].mu["dense"]["kernel"]
        assert "fsdp" in str(mu.sharding.spec)


def test_gathered_save_equals_sharded_save_values(tmp_path):
    """gather_for_checkpoint first (layout-independent checkpoint): the
    manifest is valid and a replicated restore matches the sharded-save
    path bit for bit."""
    params = _params()
    part = SPMDPartitioner(make_mesh(dp=1, fsdp=8), GENERIC_RULES,
                           zero_axis="fsdp")
    state = _state(part, params)
    gathered = part.gather_for_checkpoint(state)
    assert all(
        leaf.sharding.is_fully_replicated
        for leaf in jax.tree_util.tree_leaves(gathered))
    with CheckpointManager(str(tmp_path / "g")) as mgr:
        mgr.save(1, gathered)
        mgr.wait()
        assert mgr.verify(1) is True
        dp = DataParallelPartitioner(make_mesh(dp=8))
        got = mgr.restore(template=_state(dp, params))
        np.testing.assert_array_equal(
            np.asarray(got["params"]["dense"]["kernel"]),
            np.asarray(params["dense"]["kernel"]))


@pytest.fixture
def verdict_put_back():
    """The manager publishes its last verdict process-wide
    (``checkpoint_integrity``). A corrupt one left behind reads as
    ``degraded`` in every later ``/healthz`` of this worker, and in
    collection order ``tests/serving/test_brownout.py`` is later."""
    from sparkdl_tpu.observability import flight

    before = flight.health_facts().get("checkpoint_integrity")
    yield
    flight.set_health_fact("checkpoint_integrity", before)


def test_corrupt_sharded_checkpoint_detected(tmp_path, verdict_put_back):
    """Integrity detection is layout-blind: flip a byte in a sharded
    save and restore must refuse it (pinned step -> typed error)."""
    import os

    from sparkdl_tpu.checkpoint.manager import CheckpointCorruptError

    part = DataParallelPartitioner(make_mesh(dp=4, fsdp=2),
                                   zero_axis="fsdp")
    state = _state(part, _params())
    with CheckpointManager(str(tmp_path)) as mgr:
        mgr.save(1, state)
        mgr.wait()
        # flip bytes in one landed file of the step dir
        step_dir = tmp_path / "1"
        victims = [p for p in step_dir.rglob("*") if p.is_file()]
        target = max(victims, key=lambda p: p.stat().st_size)
        data = bytearray(target.read_bytes())
        data[len(data) // 2] ^= 0xFF
        target.write_bytes(bytes(data))
        assert mgr.verify(1) is False
        with pytest.raises(CheckpointCorruptError):
            mgr.restore(1, template=state)
