"""The elastic step donates its train state on a mesh one process owns,
and on no other (parallel/elastic.py ``state_donation``).

- a step built for a process-local mesh deletes the state it is given
  and says so in its lowering (``describe_step()["donated_inputs"]``),
  on the shard_map plane and on the pjit dense plane;
- the trainer keeps nothing donation deletes: ``snapshot()``,
  ``validate()`` and ``version`` after unsynced donating steps read the
  newest state, and a state lost to a failed step falls back to the
  host snapshot, never to a deleted array;
- establish on such a mesh puts the host tree onto it once: leaf for
  leaf the host's values, and no second device copy of any leaf alive
  when it returns (``place_from_host``; the broadcast a mesh that spans
  processes needs holds the stacked offer beside the picked copy);
- on a mesh that spans processes (a real two-process world) the input
  survives, the lowering donates nothing, ``_checked_ts`` is kept as
  before, and a world of one re-formed as a world of two carries its
  state over.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import optax
import pytest

import jax
from jax.sharding import Mesh

import elasticdl_tpu.parallel.distributed as dist_mod
from elasticdl_tpu.parallel.distributed import WorldSpec
from elasticdl_tpu.parallel.elastic import ElasticDPTrainer, state_donation
from model_zoo.transformer_lm import transformer_lm as tzoo

HERE = os.path.dirname(os.path.abspath(__file__))
KW = dict(
    vocab_size=32,
    num_layers=1,
    num_heads=2,
    head_dim=8,
    embed_dim=16,
    mlp_dim=32,
    use_flash=False,
)
ROWS = 16
SPEC = WorldSpec(coordinator="", num_processes=1, process_id=0, epoch=0)


def _batch(seed=0):
    toks = np.random.default_rng(seed).integers(0, 32, (ROWS, 8))
    toks = toks.astype(np.int32)
    return {"tokens": toks}, toks.copy()


def _trainer(plane):
    """An established trainer on the 8-device CPU mesh: the shard_map
    step (``make_elastic_train_step``) or, with tensor-parallel specs,
    the GSPMD one (``make_pjit_train_step``)."""
    if plane == "pjit":
        trainer = ElasticDPTrainer(
            tzoo.custom_model(**KW),
            tzoo.loss,
            optax.sgd(0.05),
            distributed_builder=lambda mesh: (
                tzoo.custom_model(**KW),
                tzoo.param_shardings(mesh, tensor_parallel=2),
            ),
            mesh_axes_fn=lambda n: tzoo.mesh_axes(n, tensor_parallel=2),
        )
    else:
        trainer = ElasticDPTrainer(
            tzoo.custom_model(**KW), tzoo.loss, optax.sgd(0.05)
        )
    trainer.default_minibatch_size = ROWS
    trainer.establish(SPEC, example_batch=_batch())
    assert trainer._pjit_dense == (plane == "pjit")
    return trainer


@pytest.fixture
def singleton_world(monkeypatch):
    """establish() without jax.distributed (test_dense_sharding's
    bypass for single-process worlds)."""
    monkeypatch.setattr(dist_mod, "ensure_world", lambda s, **k: None)


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def test_donation_is_read_off_the_mesh():
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    assert not mesh.is_multi_process
    assert state_donation(mesh) == (0,)


@pytest.mark.parametrize("plane", ["shard_map", "pjit"])
def test_process_local_step_deletes_its_input_state(singleton_world, plane):
    trainer = _trainer(plane)
    try:
        facts = trainer.describe_step()
        assert facts["donated_inputs"] == len(_leaves(trainer._ts)) > 0
        given = trainer._ts
        trainer.train_step(*_batch(1), ROWS, sync=True)
        assert all(leaf.is_deleted() for leaf in _leaves(given))
        assert not any(leaf.is_deleted() for leaf in _leaves(trainer._ts))
    finally:
        trainer.close()


@pytest.mark.parametrize("plane", ["shard_map", "pjit"])
def test_trainer_keeps_nothing_donation_deletes(singleton_world, plane):
    """Several unsynced donating steps, then every reader the worker
    calls at a pause: none touches a deleted array, all see the newest
    state."""
    trainer = _trainer(plane)
    try:
        assert trainer._checked_ts is None  # establish kept none
        for i in range(5):
            trainer.train_step(*_batch(i), ROWS, sync=False)
        assert trainer.validate() is True
        assert trainer._checked_ts is None
        assert trainer.version == 5
        assert trainer.state_device_coverage() == 8
        assert len(trainer.drain_metrics()) == 5
        if plane == "shard_map":
            host = trainer.snapshot()
            assert int(np.asarray(host.version)) == 5
            # a synced step keeps none either, and the snapshot taken
            # before it is still readable host data afterwards
            trainer.train_step(*_batch(9), ROWS, sync=True)
            assert trainer._checked_ts is None
            assert int(np.asarray(host.version)) == 5
            assert int(np.asarray(trainer.snapshot().version)) == 6
    finally:
        trainer.close()


def _shapes(arrays):
    """(shape, dtype) -> how many of ``arrays`` have it, scalars and
    other small arrays left out (keys, counters and the version are
    not the state's weight)."""
    counted = {}
    for a in arrays:
        if a.size >= 16:
            key = (tuple(a.shape), str(a.dtype))
            counted[key] = counted.get(key, 0) + 1
    return counted


def test_establish_places_the_state_once(singleton_world, monkeypatch):
    """Parameters and both AdamW moments: what is alive on the devices
    when establish returns is one array a leaf, replicated, with the
    host tree's values, and nothing of a leaf's shape or of a stacked
    offer's ((devices,) + shape) beside it. The broadcast is not
    called."""
    import elasticdl_tpu.parallel.elastic as elastic_mod

    def no_broadcast(*args, **kwargs):
        raise AssertionError("establish broadcast on a mesh one process owns")

    monkeypatch.setattr(elastic_mod, "broadcast_from_device0", no_broadcast)
    # on the CPU backend a host copy is a VIEW of the device's own
    # buffer and keeps the init's arrays alive; on a chip it is host
    # memory and they go. Copy, so that here they go too
    view = elastic_mod.host_copy
    monkeypatch.setattr(
        elastic_mod,
        "host_copy",
        lambda tree: jax.tree_util.tree_map(np.array, view(tree)),
    )
    before = {id(a) for a in jax.live_arrays()}
    trainer = ElasticDPTrainer(
        tzoo.custom_model(**KW), tzoo.loss, optax.adamw(1e-3)
    )
    trainer.default_minibatch_size = ROWS
    trainer.establish(SPEC, example_batch=_batch())
    try:
        placed = _leaves(trainer._ts)
        n = trainer.mesh.devices.size
        assert n == 8 and not trainer.mesh.is_multi_process
        # counted before a shard is looked at: its view is an array too
        alive = [a for a in jax.live_arrays() if id(a) not in before]
        for leaf, host in zip(placed, _leaves(trainer._host_ts)):
            assert leaf.sharding.is_fully_replicated
            assert len(leaf.addressable_shards) == n
            for shard in leaf.addressable_shards:
                np.testing.assert_array_equal(np.asarray(shard.data), host)
        assert _shapes(alive) == _shapes(placed)
        stacked = {((n,) + shape, dtype) for shape, dtype in _shapes(placed)}
        assert not stacked & set(_shapes(alive))
        # and the step it built trains from them
        loss, _, _ = trainer.train_step(*_batch(1), ROWS, sync=True)
        assert np.isfinite(loss)
    finally:
        trainer.close()


def test_place_from_host_is_for_a_mesh_one_process_owns():
    from elasticdl_tpu.parallel.elastic import place_from_host

    class Spanning:
        is_multi_process = True

    with pytest.raises(ValueError, match="spans processes"):
        place_from_host(Spanning(), {"w": np.zeros(4)})
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    tree = {"w": np.arange(32.0, dtype=np.float32), "n": np.int32(3)}
    placed = place_from_host(mesh, tree)
    assert placed["w"].dtype == np.float32 and placed["n"].dtype == np.int32
    assert all(leaf.sharding.is_fully_replicated for leaf in _leaves(placed))
    np.testing.assert_array_equal(np.asarray(placed["w"]), tree["w"])


def test_snapshot_after_a_lost_state_is_the_host_snapshot(singleton_world):
    """A step that fails on a process-local mesh may take the donated
    state with it: snapshot() then answers with the latest host
    snapshot, not with an error from a deleted array."""
    trainer = _trainer("shard_map")
    try:
        trainer.train_step(*_batch(1), ROWS, sync=True)
        kept = trainer.snapshot()
        for leaf in _leaves(trainer._ts):
            leaf.delete()
        assert trainer.snapshot() is kept
        assert int(np.asarray(kept.version)) == 1
    finally:
        trainer.close()


def test_sharded_save_reads_the_newest_state(singleton_world, tmp_path):
    """The checkpoint's device-to-host phase runs between two
    dispatches: it reads the state the last step returned, and the next
    step may donate that state while the files are still being
    written."""
    from elasticdl_tpu.common.sharded_checkpoint import (
        ShardedCheckpointManager,
    )

    trainer = _trainer("pjit")
    ckpt = ShardedCheckpointManager(
        str(tmp_path), checkpoint_steps=1, async_io=True
    )
    try:
        for i in range(3):
            trainer.train_step(*_batch(i), ROWS, sync=False)
        ckpt.save(trainer._ts, trainer.version)
        trainer.train_step(*_batch(7), ROWS, sync=True)
        ckpt.wait()
        assert ckpt.versions() == [3]
        assert trainer.restore_sharded(ckpt.latest_dir()) == 3
        assert trainer._checked_ts is None
        assert trainer.version == 3
        trainer.train_step(*_batch(8), ROWS, sync=True)
        assert trainer.version == 4
    finally:
        ckpt.close()
        trainer.close()


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def two_process_world():
    """Rank 0 trains alone, pauses, and re-forms with a fresh rank 1
    (tests/elastic_donation_world.py); each member's account."""
    ports = [str(_free_port()), str(_free_port())]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    members = [
        subprocess.Popen(
            [sys.executable, os.path.join(HERE, "elastic_donation_world.py")]
            + [str(pid)] + ports,
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for pid in (0, 1)
    ]  # fmt: skip
    accounts = []
    try:
        for member in members:
            out, err = member.communicate(timeout=300)
            assert member.returncode == 0, err[-3000:]
            accounts.append(json.loads(out.splitlines()[-1]))
    finally:
        for member in members:
            if member.poll() is None:
                member.kill()
                member.wait(timeout=30)
    return accounts


def test_a_world_of_one_donates_then_carries_its_state_into_a_world_of_two(
    two_process_world,
):
    rank0, rank1 = two_process_world
    assert rank0["alone_multi_process"] is False
    assert rank0["alone_donated_inputs"] == rank0["leaves"] > 0
    assert rank0["alone_input_deleted"] is True
    assert rank0["alone_validated"] is True
    assert rank0["alone_checked_kept"] is False
    assert rank0["alone_version"] == 3
    # the pause's host snapshot reached both members of the new world
    assert rank0["pair_version_carried"] == 3
    assert rank1["pair_version_carried"] == 3
    assert rank0["pair_version"] == rank1["pair_version"] == 5


@pytest.mark.parametrize("rank", [0, 1])
def test_a_mesh_that_spans_processes_keeps_its_input_state(
    two_process_world, rank
):
    account = two_process_world[rank]
    assert account["pair_multi_process"] is True
    # the multi-process lowering is what it was: nothing donated, by
    # either builder
    assert account["pair_donated_inputs"] == 0
    assert account["pair_pjit_donated_inputs"] == 0
    assert account["pair_input_deleted"] is False
    # and the rollback state is kept as before
    assert account["pair_checked_is_newest"] is True
    assert account["pair_validated"] is True


@pytest.mark.parametrize("rank", [0, 1])
def test_a_lagged_validation_keeps_the_validated_state_not_the_newest(
    two_process_world, rank
):
    """``settle(lag=1)`` with two steps dispatched on versions 5 -> 7:
    one loss is handed out, one step stays in flight, and the state a
    failed collective would roll back to is the validated step's (alive:
    this mesh donates nothing), one version behind the newest."""
    account = two_process_world[rank]
    assert account["lagged_losses"] == account["lagged_in_flight"] == 1
    assert account["lagged_checked_is_newest"] is False
    assert account["lagged_checked_deleted"] is False
    assert account["lagged_checked_version"] == 6
    assert account["lagged_validated_version"] == 6  # the receipt's
    assert account["lagged_newest_version"] == 7
    # and the pause settles the step left in flight
    assert account["pair_validated"] is True
    assert account["settled_losses"] == 1
