"""Tiered embedding store (ps/tiered_store.py, docs/tiered_store.md).

Three layers of contract:

- **value transparency**: a tiered table is bitwise-indistinguishable
  from the untiered table it wraps — lazy init, overwrite, snapshot
  cuts — no matter how rows shuffle between warm and disk, on both the
  host dict store and the ``--ps_device`` arena;
- **crash consistency**: a spill segment IS a PR-10 snapshot shard, so
  a torn/manifest-less segment is invisible (the previous generation
  serves), and a demotion killed between manifest-seal and index-flip
  never loses a row (it lives in warm until the flip);
- **signals**: the delta-log ``note_applied`` pin ring and read pins
  block eviction of hot rows; the HotRowCache per-table counters feed
  the admission telemetry; the servicer aggregates tier counters into
  ``ps_status``.

Most tests stop the background demoter (``close()``) and drive
``_demote_once()`` directly so every spill is deterministic; the
thread-driven path is exercised through the Parameters/servicer
integration test.
"""

import collections
import os
import time

import numpy as np
import optax
import pytest

from elasticdl_tpu.common.tensor import Tensor
from elasticdl_tpu.nn.comm_plane import HotRowCache
from elasticdl_tpu.ps.embedding_table import EmbeddingTable
from elasticdl_tpu.ps.parameters import EmbeddingTableInfo, Parameters
from elasticdl_tpu.ps.servicer import PserverServicer
from elasticdl_tpu.ps.snapshot import (
    snapshot_versions,
    write_shard_snapshot,
)
from elasticdl_tpu.ps.tiered_store import TieredEmbeddingTable

DIM = 4


def _tiered(tmp_path, warm_rows=8, name="emb", init="zeros", inner=None,
            background=False):
    if inner is None:
        inner = EmbeddingTable(name, DIM, init)
    t = TieredEmbeddingTable(
        inner, os.path.join(str(tmp_path), "spill-" + name), warm_rows
    )
    if not background:
        t.close()  # tests drive _demote_once() deterministically
    return t


def _rows_for(ids, base=0.0):
    ids = np.asarray(ids, dtype=np.int64).reshape(-1)
    return (
        ids.astype(np.float32)[:, None] + np.float32(base)
    ) * np.ones((1, DIM), np.float32)


def _fill(t, n, base=0.0):
    ids = np.arange(n, dtype=np.int64)
    rows = _rows_for(ids, base)
    t.set(ids, rows)
    return ids, rows


def _drain(t):
    while t._demote_once():
        pass


def _await_disk_rows(table, timeout=10.0):
    """Press the BACKGROUND demoter until rows are resident on disk.
    Mid-apply spills of a step's own rows are legal and get superseded
    by the apply's warm write (set pops the disk entry), so "a spill
    happened" is not enough."""
    deadline = time.time() + timeout
    while time.time() < deadline and not table.stats()["disk_rows"]:
        table.signal_pressure()
        time.sleep(0.02)
    assert table.stats()["disk_rows"] > 0


# ---------------------------------------------------------------------------
# value transparency
# ---------------------------------------------------------------------------


def test_spill_then_cold_pull_roundtrip(tmp_path):
    t = _tiered(tmp_path, warm_rows=8)
    ids, rows = _fill(t, 32)
    _drain(t)
    s = t.stats()
    assert s["spilled_rows"] > 0 and s["spill_segments"] > 0
    assert t.warm_len() <= 8
    assert len(t) == 32  # logical size counts both tiers
    # warm and disk are disjoint
    warm = set(t._inner.embedding_vectors)
    assert not warm & set(t._disk)
    assert set(t._ticks) == warm
    # a full pull promotes the cold rows back, values intact
    np.testing.assert_array_equal(t.get(ids), rows)
    s = t.stats()
    assert s["cold_pull_rows"] > 0 and s["cold_pull_segments"] > 0
    assert s["promoted_rows"] > 0
    assert t.stats()["disk_rows"] == 0


def test_cold_pulls_are_batched_per_segment(tmp_path):
    t = _tiered(tmp_path, warm_rows=4)
    _fill(t, 16)
    _drain(t)
    segments = t.stats()["spill_segments"]
    assert segments >= 1
    cold = sorted(t._disk)
    t.get(np.asarray(cold, dtype=np.int64))
    s = t.stats()
    # one segment OPEN per cold cluster, never one per row
    assert s["cold_pull_segments"] <= segments
    assert s["cold_pull_rows"] == len(cold)


def test_values_match_untiered_table(tmp_path):
    t = _tiered(tmp_path, warm_rows=4, init="uniform")
    plain = EmbeddingTable("emb", DIM, "uniform")
    batches = [
        [1, 2, 3],
        [10, 11, 12, 13, 14],
        [1, 50, 60, 2],
        [70, 80, 90, 11, 3],
        [5, 6, 7, 8, 9, 10],
    ]
    for batch in batches:
        np.testing.assert_array_equal(t.get(batch), plain.get(batch))
        _drain(t)
    update = _rows_for([2, 60, 90], base=0.5)
    t.set([2, 60, 90], update)
    plain.set([2, 60, 90], update)
    _drain(t)
    sids, srows = t.snapshot()
    pids, prows = plain.snapshot()
    so, po = np.argsort(sids), np.argsort(pids)
    np.testing.assert_array_equal(sids[so], pids[po])
    np.testing.assert_array_equal(srows[so], prows[po])


def test_warm_write_supersedes_disk_copy(tmp_path):
    t = _tiered(tmp_path, warm_rows=2)
    _fill(t, 6)
    _drain(t)
    cold = sorted(t._disk)
    assert cold
    i = cold[0]
    new = np.full((1, DIM), 55.0, np.float32)
    t.set([i], new)
    assert i not in t._disk  # unindexed in the same hold as the write
    np.testing.assert_array_equal(t.get([i]), new)


# ---------------------------------------------------------------------------
# snapshot round-trips across tier configurations
# ---------------------------------------------------------------------------


def test_snapshot_roundtrip_tiered_to_plain(tmp_path):
    t = _tiered(tmp_path, warm_rows=4)
    ids, rows = _fill(t, 16)
    _drain(t)
    assert t.stats()["disk_rows"] > 0
    sids, srows = t.snapshot()
    assert len(sids) == 16
    plain = EmbeddingTable("emb", DIM, "zeros")
    plain.load_snapshot(sids, srows)
    np.testing.assert_array_equal(plain.get(list(ids)), rows)


def test_snapshot_roundtrip_plain_to_tiered(tmp_path):
    plain = EmbeddingTable("emb", DIM, "zeros")
    ids = np.arange(16, dtype=np.int64)
    rows = _rows_for(ids, base=7.0)
    plain.set(ids, rows)

    t = _tiered(tmp_path, warm_rows=4)
    _fill(t, 6, base=100.0)  # pre-restore junk, some of it spilled
    _drain(t)
    spill_dir = t._dir
    assert snapshot_versions(spill_dir)

    t.load_snapshot(*plain.snapshot())
    # the snapshot supersedes the disk tier entirely
    assert t.stats()["disk_rows"] == 0
    assert not snapshot_versions(spill_dir)
    np.testing.assert_array_equal(t.get(ids), rows)
    # the demoter re-spills overflow afterwards, values unchanged
    _drain(t)
    assert t.stats()["disk_rows"] > 0
    np.testing.assert_array_equal(t.get(ids), rows)


# ---------------------------------------------------------------------------
# crash consistency (the PR-10 segment format doing double duty)
# ---------------------------------------------------------------------------


def test_reattach_serves_spilled_rows_newest_generation_wins(tmp_path):
    t = _tiered(tmp_path, warm_rows=2, name="emb")
    ids, _ = _fill(t, 4)
    _drain(t)
    # promote everything, overwrite, spill again -> a NEWER generation
    # holds the current values; stale generations linger on disk
    t.get(ids)
    rows_v2 = _rows_for(ids, base=100.0)
    t.set(ids, rows_v2)
    _drain(t)
    assert len(snapshot_versions(t._dir)) >= 2
    # the warm tier is volatile: only rows cold at "crash" time have
    # their CURRENT value on disk (a row still warm here may resolve
    # to its older generation after re-attach, and that is correct)
    cold_now = dict(t._disk)
    assert cold_now

    t2 = _tiered(tmp_path, warm_rows=2, name="emb")
    # index agrees before any promoting get: same id -> same (newest)
    # generation the live table had it in
    for i, gen in cold_now.items():
        assert t2._disk[i] == gen
    cold = sorted(cold_now)
    np.testing.assert_array_equal(
        t2.get(cold), _rows_for(cold, base=100.0)
    )


def test_torn_and_manifestless_segments_previous_generation_serves(
    tmp_path,
):
    t = _tiered(tmp_path, warm_rows=2, name="emb")
    ids, _ = _fill(t, 4)
    _drain(t)
    gen1 = snapshot_versions(t._dir)
    assert gen1
    t.get(ids)
    t.set(ids, _rows_for(ids, base=100.0))
    _drain(t)
    gens = snapshot_versions(t._dir)
    newest = max(gens)
    assert newest > max(gen1)

    # a torn mid-write temp dir (crash before the atomic rename)
    torn = os.path.join(t._dir, "tmp-snap_v%d.123" % (newest + 1))
    os.makedirs(torn)
    with open(os.path.join(torn, "tables.npz"), "wb") as f:
        f.write(b"torn bytes")
    # ... and strip the NEWEST sealed generation's manifest: an
    # unpublished segment must be invisible to re-attach
    os.remove(
        os.path.join(t._dir, "snap_v%d" % newest, "manifest.json")
    )

    t2 = _tiered(tmp_path, warm_rows=2, name="emb")
    cold = sorted(t2._disk)
    assert cold
    assert all(gen < newest for gen in t2._disk.values())
    # the previous generation's (pre-overwrite) values serve
    np.testing.assert_array_equal(t2.get(cold), _rows_for(cold))


def test_crash_between_seal_and_index_keeps_row_warm(tmp_path):
    """A demoter killed after phase 2 (segment sealed) but before
    phase 3 (index flip): the victim is still warm, the sealed segment
    is unindexed — reads and snapshots never see the stale copy."""
    t = _tiered(tmp_path, warm_rows=8, name="emb")
    ids, rows = _fill(t, 4)
    stale = {
        "version": 50,
        "initialized": True,
        "dense": {},
        "tables": {
            "emb": {
                "ids": np.array([0], dtype=np.int64),
                "rows": np.full((1, DIM), 123.0, np.float32),
                "dim": DIM,
                "initializer": "zeros",
                "is_slot": False,
            }
        },
    }
    write_shard_snapshot(t._dir, stale)
    np.testing.assert_array_equal(t.get([0]), rows[:1])
    sids, srows = t.snapshot()
    assert int((sids == 0).sum()) == 1
    np.testing.assert_array_equal(srows[sids == 0], rows[:1])


def test_failed_segment_write_keeps_rows_warm(tmp_path, monkeypatch):
    import elasticdl_tpu.ps.tiered_store as ts

    t = _tiered(tmp_path, warm_rows=2)
    ids, rows = _fill(t, 6)

    def boom(*a, **kw):
        raise OSError("disk full")

    monkeypatch.setattr(ts, "write_shard_snapshot", boom)
    assert t._demote_once() == 0
    assert t.warm_len() == 6
    assert t.stats()["disk_rows"] == 0
    np.testing.assert_array_equal(t.get(ids), rows)


def test_row_touched_mid_spill_stays_warm(tmp_path, monkeypatch):
    """Phase 3 verifies ticks: a victim written to between capture and
    seal keeps its warm row; the segment's stale copy is never
    indexed."""
    import elasticdl_tpu.ps.tiered_store as ts

    t = _tiered(tmp_path, warm_rows=2)
    _fill(t, 6)
    real = ts.write_shard_snapshot
    hit = {}

    def touching_write(directory, state, **kw):
        seg = next(iter(state["tables"].values()))
        victim = int(np.asarray(seg["ids"]).reshape(-1)[0])
        hit["victim"] = victim
        # phase 2 holds no lock, so this concurrent write is legal
        t.set([victim], np.full((1, DIM), 777.0, np.float32))
        return real(directory, state, **kw)

    monkeypatch.setattr(ts, "write_shard_snapshot", touching_write)
    t._demote_once()
    victim = hit["victim"]
    assert victim not in t._disk
    assert victim in t._inner.embedding_vectors
    np.testing.assert_array_equal(
        t.get([victim]), np.full((1, DIM), 777.0, np.float32)
    )


# ---------------------------------------------------------------------------
# eviction signals
# ---------------------------------------------------------------------------


def test_note_applied_pins_recent_rows_against_demotion(tmp_path):
    t = _tiered(tmp_path, warm_rows=2)
    _fill(t, 8)
    t.note_applied([0, 1], version=5)
    _drain(t)
    # recently-applied rows survived the spill; everything else went
    assert 0 in t._inner.embedding_vectors
    assert 1 in t._inner.embedding_vectors
    assert 0 not in t._disk and 1 not in t._disk
    assert t.stats()["disk_rows"] == 6
    # the pin ring prunes pin_versions (=2) behind the clock: after
    # version 30 only the fresh note still pins
    t.note_applied([0], version=30)
    _drain(t)
    assert 1 in t._disk
    assert 0 not in t._disk


def test_read_pins_block_eviction(tmp_path):
    t = _tiered(tmp_path, warm_rows=2)
    _fill(t, 6)
    with t._mu:
        t._pins.update([3])
    _drain(t)
    assert 3 in t._inner.embedding_vectors and 3 not in t._disk
    with t._mu:
        t._pins.subtract([3])
        t._pins += collections.Counter()
    # fresh pressure with the pin released: 3 is now the oldest victim
    t.set([100, 101], _rows_for([100, 101]))
    _drain(t)
    assert 3 in t._disk


def test_cold_note_applied_never_fabricates_a_warm_victim(tmp_path):
    """A signal-only touch of a DISK-resident id (note_applied from the
    delta log) must not plant it in the warm recency index — the
    demoter would lazy-init a fresh row and seal THAT over the real
    value in a newer generation."""
    t = _tiered(tmp_path, warm_rows=2)
    ids, rows = _fill(t, 6)
    _drain(t)
    cold = sorted(t._disk)
    assert cold
    t.note_applied(cold, version=9)
    assert not set(cold) & set(t._ticks)
    # fresh pressure on NEW ids, then spill again: the cold rows must
    # come back with their spilled values, not lazy re-inits
    t.set([100, 101], _rows_for([100, 101], base=50.0))
    _drain(t)
    np.testing.assert_array_equal(t.get(cold), _rows_for(cold))


def test_hit_rate_signal_sets_eviction_depth(tmp_path):
    t = _tiered(tmp_path, warm_rows=10)
    _fill(t, 12)
    # no pulls yet -> hit rate 1.0 -> demote below budget for headroom
    with t._mu:
        assert t._demote_target_locked() == 9
    _drain(t)
    assert t.warm_len() == 9
    # force cold pulls until the hit rate drops below the slack gate:
    # a thrashing table keeps its full budget
    t.get(sorted(t._disk))
    _drain(t)
    while True:
        s = t.stats()
        pulls = s["warm_hit_rows"] + s["cold_pull_rows"]
        if pulls and s["warm_hit_rows"] / pulls < 0.98:
            break
        cold = sorted(t._disk)
        assert cold, "expected cold rows to pull"
        t.get(cold)
        _drain(t)
    with t._mu:
        assert t._demote_target_locked() == 10


# ---------------------------------------------------------------------------
# the device arm (arena inner, virtual CPU devices from conftest)
# ---------------------------------------------------------------------------


def test_device_tiered_matches_host_table(tmp_path):
    from elasticdl_tpu.ps.device_store import DeviceEmbeddingTable

    inner = DeviceEmbeddingTable("demb", DIM, "uniform")
    t = _tiered(tmp_path, warm_rows=4, name="demb", inner=inner)
    host = EmbeddingTable("demb", DIM, "uniform")
    ids = np.arange(12, dtype=np.int64)
    np.testing.assert_array_equal(t.get(ids), host.get(list(ids)))
    _drain(t)
    assert t.stats()["disk_rows"] > 0
    assert t.warm_len() <= 4
    # cold pulls promote through the arena, bitwise-identical
    np.testing.assert_array_equal(t.get(ids), host.get(list(ids)))
    # snapshot round-trip device-tiered -> plain host table
    _drain(t)
    sids, srows = t.snapshot()
    plain = EmbeddingTable("demb", DIM, "uniform")
    plain.load_snapshot(sids, srows)
    np.testing.assert_array_equal(plain.get(list(ids)), host.get(list(ids)))


def test_device_tiered_ensure_rows_promotes_before_lazy_init(tmp_path):
    from elasticdl_tpu.ps.device_store import DeviceEmbeddingTable

    inner = DeviceEmbeddingTable("demb", DIM, "zeros")
    t = _tiered(tmp_path, warm_rows=2, name="demb", inner=inner)
    ids = np.arange(6, dtype=np.int64)
    rows = _rows_for(ids, base=3.0)
    t.set(ids, rows)
    _drain(t)
    cold = sorted(t._disk)
    assert cold
    # the jitted-apply path: ensure_rows must surface the DISK values
    # in the arena, not zero-init fresh slots
    slots = t.ensure_rows(np.asarray(cold, dtype=np.int64))
    assert len(slots) == len(cold)
    np.testing.assert_array_equal(t.get(cold), _rows_for(cold, base=3.0))


def test_device_free_list_keeps_arena_at_warm_size(tmp_path):
    from elasticdl_tpu.ps.device_store import DeviceEmbeddingTable

    inner = DeviceEmbeddingTable("demb", DIM, "zeros")
    t = _tiered(tmp_path, warm_rows=8, name="demb", inner=inner)
    for batch in range(16):
        ids = np.arange(batch * 8, batch * 8 + 8, dtype=np.int64)
        t.get(ids)
        _drain(t)
    # 128 distinct ids cycled through; without slot reuse the arena
    # would have doubled past _MIN_CAPACITY
    assert int(inner._arena.shape[0]) == 64
    assert len(t) == 128


def test_device_missing_and_evict_rows():
    from elasticdl_tpu.ps.device_store import DeviceEmbeddingTable

    d = DeviceEmbeddingTable("x", DIM, "ones")
    d.get(np.arange(10, dtype=np.int64))
    assert d.missing_ids([5, 99]) == [99]
    assert len(d) == 10  # the probe must not lazy-init
    freed = {d._slots[3], d._slots[4]}
    assert d.evict_rows([3, 4, 777]) == 2
    assert len(d) == 8
    assert set(d._free) == freed
    # a reused slot is written before any read
    got = d.get(np.asarray([100, 101], dtype=np.int64))
    np.testing.assert_array_equal(got, np.ones((2, DIM), np.float32))
    assert not d._free


def test_host_missing_and_evict_rows():
    e = EmbeddingTable("x", 3, "zeros")
    e.get([1, 2, 3])
    assert e.missing_ids([2, 9]) == [9]
    assert len(e) == 3  # the probe must not lazy-init
    assert e.evict_rows([1, 9]) == 1
    assert 1 not in e.embedding_vectors


# ---------------------------------------------------------------------------
# HotRowCache per-table counters (the top tier's admission signal)
# ---------------------------------------------------------------------------


def test_hot_row_cache_per_table_counters():
    c = HotRowCache(max_rows=2, window=1)
    c.note_version("ps0", 1)
    row = np.ones(DIM, np.float32)
    c.put("emb_a", 1, "ps0", 1, row)
    assert c.get("emb_a", 1) is not None  # hit
    assert c.get("emb_a", 2) is None  # miss
    assert c.get("emb_b", 7) is None  # miss, other table
    # capacity eviction charges the VICTIM's table
    c.put("emb_b", 8, "ps0", 1, row)
    c.put("emb_b", 9, "ps0", 1, row)  # evicts emb_a:1 (LRU)
    stats = c.table_stats()
    assert stats["emb_a"] == {"hits": 1, "misses": 1, "evictions": 1}
    assert stats["emb_b"]["misses"] == 1
    assert stats["emb_b"]["evictions"] == 0
    # the aggregate series existing readers consume stays coherent
    assert c.hits == 1 and c.misses == 2


def test_worker_telemetry_exports_labeled_cache_series():
    from elasticdl_tpu.utils import profiling
    from elasticdl_tpu.worker.telemetry import WorkerTelemetry

    cache = HotRowCache(max_rows=4, window=1)
    cache.note_version("ps0", 1)
    cache.put("emb", 1, "ps0", 1, np.ones(DIM, np.float32))
    cache.get("emb", 1)
    cache.get("emb", 2)

    class _Client:
        hot_row_cache = cache

    tel = WorkerTelemetry(worker_id=3, ps_client=_Client())
    snap = tel.maybe_snapshot(force=True)
    assert snap["cache_tables"]["emb"]["hits"] == 1
    assert snap["cache_tables"]["emb"]["misses"] == 1
    text = profiling.metrics.prometheus_text()
    assert 'edl_cache_hits_total{table="emb",worker="3"} 1' in text
    assert 'edl_cache_misses_total{table="emb",worker="3"} 1' in text


# ---------------------------------------------------------------------------
# Parameters / servicer integration
# ---------------------------------------------------------------------------


def test_parameters_tier_config_wraps_row_and_slot_tables(tmp_path):
    p = Parameters(
        tier_config={
            "warm_rows": 4,
            "spill_dir": os.path.join(str(tmp_path), "spill"),
        }
    )
    try:
        p.init_from_model(
            0,
            {"w": np.zeros((2, 2), np.float32)},
            [EmbeddingTableInfo("emb", DIM, "zeros")],
        )
        assert isinstance(p.embedding_params["emb"], TieredEmbeddingTable)
        p.create_slot_params(["m"], {"m": 0.0})
        assert isinstance(
            p.embedding_params["emb-m"], TieredEmbeddingTable
        )
        # restore swaps in replacement tiered tables over the same
        # spill dirs; the outgoing demoters must be gone first
        state = p.snapshot_state()
        p.restore_state(state)
        assert isinstance(p.embedding_params["emb"], TieredEmbeddingTable)
    finally:
        p.close()


def test_servicer_forwards_apply_notes_and_reports_tier_stats(tmp_path):
    p = Parameters(
        tier_config={
            "warm_rows": 4,
            "spill_dir": os.path.join(str(tmp_path), "spill"),
        }
    )
    s = PserverServicer(p, 1, optax.sgd(0.1), use_async=False)
    try:
        s.push_model(
            {
                "version": 0,
                "params": [Tensor("w", np.ones((2, 2), np.float32))],
                "embedding_infos": [{"name": "emb", "dim": DIM}],
            }
        )
        for step in range(4):
            ids = np.arange(step * 8, step * 8 + 8, dtype=np.int64)
            s.push_gradient(
                {
                    "model_version": step,
                    "gradients": [
                        Tensor(
                            "emb",
                            np.ones((8, DIM), np.float32),
                            indices=ids,
                        ),
                    ],
                }
            )
        table = p.embedding_params["emb"]
        # the delta note reached the tiered table's pin ring
        assert table._applied
        # overflow exists; the BACKGROUND demoter spills it
        _await_disk_rows(table)
        assert table.stats()["spilled_rows"] > 0
        resp = s.ps_status({})
        assert resp["tiered"]["spilled_rows"] > 0
        assert resp["tiered"]["disk_rows"] > 0
        # pull the currently-cold ids back through the servicer: the
        # cold pull promotes them and the reply is well-formed
        with table._mu:
            cold = sorted(table._disk)
        assert cold
        out = s.pull_embedding_vector(
            {"name": "emb", "ids": np.asarray(cold, dtype=np.int64)}
        )
        assert out is not None
        assert s.ps_status({})["tiered"]["cold_pull_rows"] > 0
    finally:
        p.close()


def test_tiered_shard_matches_memory_shard_under_demotion_churn(tmp_path):
    """One all-in-memory and one tiered PS shard, driven from one common
    id-keyed lazy init through an identical power-law pull/push stream
    under adam (so the slot tables tier too). The tiered shard's warm
    budget is an eighth of the id pool and its BACKGROUND demoter is
    live, so rows churn between tiers on every step; lookups, applied
    rows and the final full-table read must match bitwise — a tier move
    that drops, duplicates or stales one row (or one slot row) fails
    here."""
    dim, warm_rows, pool_n, steps = 16, 64, 512, 8
    rng = np.random.default_rng(11)
    pool = rng.permutation(5383)[:pool_n]
    w = 1.0 / np.arange(1, pool_n + 1) ** 1.2
    w /= w.sum()
    stream = [
        np.unique(rng.choice(pool, size=96, p=w)).astype(np.int64)
        for _ in range(steps)
    ]

    def shard(tier):
        p = Parameters(tier_config=tier)
        s = PserverServicer(p, 1, optax.adam(0.05), use_async=True)
        s.push_model(
            {
                "version": 0,
                "params": [Tensor("w", np.ones((4, 4), np.float32))],
                "embedding_infos": [{"name": "emb", "dim": dim}],
            }
        )
        return p, s

    def rows_of(s, ids):
        return np.asarray(
            s.pull_embedding_vector({"name": "emb", "ids": ids})["rows"]
        )

    p_mem, s_mem = shard(None)
    p_tier, s_tier = shard(
        {
            "warm_rows": warm_rows,
            "spill_dir": os.path.join(str(tmp_path), "spill"),
        }
    )
    try:
        for step, ids in enumerate(stream):
            np.testing.assert_array_equal(
                rows_of(s_mem, ids), rows_of(s_tier, ids)
            )
            grad = rng.standard_normal((len(ids), dim)).astype(np.float32)
            for s in (s_mem, s_tier):
                s.push_gradient(
                    {
                        "model_version": step,
                        "gradients": [Tensor("emb", grad, indices=ids)],
                    }
                )
            np.testing.assert_array_equal(
                rows_of(s_mem, ids), rows_of(s_tier, ids)
            )
        # the full-table read has to cross the tiers, not find a lucky
        # all-warm table
        table = p_tier.embedding_params["emb"]
        _await_disk_rows(table)
        every = np.unique(np.concatenate(stream))
        np.testing.assert_array_equal(
            rows_of(s_mem, every), rows_of(s_tier, every)
        )
        stats = table.stats()
        assert stats["spilled_rows"] > 0 and stats["cold_pull_rows"] > 0
    finally:
        p_tier.close()
        p_mem.close()


def test_tiered_metrics_collector_exports_labeled_series(tmp_path):
    from elasticdl_tpu.utils import profiling

    t = TieredEmbeddingTable(
        EmbeddingTable("memb", DIM, "zeros"),
        os.path.join(str(tmp_path), "spill-memb"),
        warm_rows=2,
    )
    try:
        _fill(t, 6)
        _drain(t)
        t.get(np.arange(6, dtype=np.int64))
        text = profiling.metrics.prometheus_text()
        assert 'edl_tiered_disk_rows{table="memb"}' in text
        spilled = [
            ln
            for ln in text.splitlines()
            if ln.startswith("edl_tiered_spilled_rows_total")
            and 'table="memb"' in ln
        ]
        assert spilled and float(spilled[0].rsplit(" ", 1)[1]) > 0
    finally:
        t.close()
    # close unregisters the collector: the series disappears
    text = profiling.metrics.prometheus_text()
    assert 'edl_tiered_disk_rows{table="memb"}' not in text
