"""Elastic checkpoints: restore across a different mesh shape / world size.

The universal checkpoint layout (``resilience/elastic.py`` +
``checkpoint/checkpointer.py``): every generation's ``manifest.json`` records
the source topology (even under ``ckpt_verify=off``); restore classifies the
target topology (``ok`` / ``elastic`` / ``incompatible``) before touching
the arrays; global arrays reshard onto the target ``NamedSharding``s; and
the per-rank data cursors — streaming consumed-prefix maps, poison-skip
histories, collator carry-overs — merge (N→M, M<N) or split (M>N)
deterministically.

Acceptance drills (subprocess, CPU virtual devices, mirroring the PR 3/5
bit-exact drills): train + save on a 4-device mesh, resume on 2 and on 8
devices with the global batch held constant — the loss trajectory must be
BIT-identical to the uninterrupted 4-device control; and the composition
with PR 5 integrity — corrupt the newest generation, fall back one, AND
resume on a different mesh under ``ckpt_verify=full`` with streaming
skip-budget accounting replayed identically.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _disarm_fault_plan():
    yield
    from veomni_tpu.resilience.faults import disarm_faults

    disarm_faults()
    os.environ.pop("VEOMNI_FAULT_PLAN", None)


# ---------------------------------------------------------------------------
# classify_restore: the one verdict shared by the restore gate and the CLI
# ---------------------------------------------------------------------------

def test_classify_restore_matrix():
    from veomni_tpu.resilience.elastic import classify_restore

    topo4 = {"world_size": 4, "device_count": 4,
             "mesh": {"fsdp": 4, "tp": 1}}
    # same world, sidecars complete -> ok
    assert classify_restore(topo4, 4, rank_files=[0, 1, 2, 3])[0] == "ok"
    # data-parallel world resize with complete sidecars -> elastic (both ways)
    assert classify_restore(topo4, 2, rank_files=[0, 1, 2, 3])[0] == "elastic"
    assert classify_restore(topo4, 8, rank_files=[0, 1, 2, 3])[0] == "elastic"
    # missing sidecars make a resize unmergeable
    verdict, reason = classify_restore(topo4, 2, rank_files=[0, 2, 3])
    assert verdict == "incompatible" and "ranks [1] are missing" in reason
    # model-parallel degree change: refused with the axis named
    verdict, reason = classify_restore(
        {"world_size": 4, "mesh": {"fsdp": 2, "tp": 2}}, 4,
        target_mesh={"fsdp": 1, "tp": 4}, rank_files=[0, 1, 2, 3])
    assert verdict == "incompatible" and "'tp' changed 2 -> 4" in reason
    # mesh-only resize (same world size): arrays still need a reshard
    verdict, _ = classify_restore(
        {"world_size": 1, "device_count": 4, "mesh": {"fsdp": 4}}, 1,
        target_mesh={"fsdp": 2}, target_device_count=2, rank_files=[0])
    assert verdict == "elastic"
    # pre-elastic checkpoint: world inferred from the sidecar set — same
    # world restores, but a RESIZE is refused (the inference cannot prove
    # the set is complete: a lost highest-rank sidecar is undetectable)
    assert classify_restore(None, 1, rank_files=[0])[0] == "ok"
    assert classify_restore(None, 2, rank_files=[0, 1])[0] == "ok"
    verdict, reason = classify_restore(None, 1, rank_files=[0, 1])
    assert verdict == "incompatible" and "no recorded topology" in reason
    # nothing recorded at all: unknown, never a hard failure
    assert classify_restore(None, 4)[0] == "unknown"
    # torn sidecar set at the same world size
    assert classify_restore({"world_size": 1}, 1,
                            rank_files=[7])[0] == "incompatible"
    # the save recorded how many sidecars it wrote: losing ALL of them is
    # as detectable as losing one (a bare listing can't tell "all lost"
    # from "none saved")
    topo_rs = {"world_size": 2, "rank_state_files": 2}
    assert classify_restore(topo_rs, 1, rank_files=None)[0] == "incompatible"
    assert classify_restore(topo_rs, 1, rank_files=[0])[0] == "incompatible"
    assert classify_restore(topo_rs, 1, rank_files=[0, 1])[0] == "elastic"


# ---------------------------------------------------------------------------
# merge/split: native loader cursors + collator carry-over
# ---------------------------------------------------------------------------

def _native_state(cursor, pending, epoch=0, seed=1, dropped=0):
    return {"dataloader": {
        "epoch": epoch, "cursor": cursor, "seed": seed,
        "dp_rank": 0, "dp_size": 2,
        "collator": {"pending": pending, "dropped_oversized": dropped},
    }}


def test_merge_split_native_loader_states():
    from veomni_tpu.resilience.elastic import (
        merge_rank_states,
        split_rank_state,
    )

    merged = merge_rank_states({
        0: _native_state(10, ["a", "b"], dropped=1),
        1: _native_state(12, ["c"]),
    })
    assert merged["saved_world_size"] == 2
    assert merged["dataloader"]["global_cursor"] == 22
    # same-world split is a bit-exact passthrough of the original docs
    assert split_rank_state(merged, 2, 0) == _native_state(10, ["a", "b"], dropped=1)
    assert split_rank_state(merged, 2, 1) == _native_state(12, ["c"])
    # 2 -> 1: global position preserved, carry-over concatenated, drop count kept
    one = split_rank_state(merged, 1, 0)["dataloader"]
    assert one["cursor"] == 22
    assert one["collator"]["pending"] == ["a", "b", "c"]
    assert one["collator"]["dropped_oversized"] == 1
    # 2 -> 4: carry-over redistributes round-robin, nothing lost/duplicated;
    # the cursor split is remainder-preserving (sums back to exactly 22)
    quarters = [split_rank_state(merged, 4, r)["dataloader"] for r in range(4)]
    assert [q["cursor"] for q in quarters] == [6, 6, 5, 5]
    got = [s for q in quarters for s in q["collator"]["pending"]]
    assert sorted(got) == ["a", "b", "c"]
    assert sum(q["collator"]["dropped_oversized"] for q in quarters) == 1

    # a torn rank set refuses to merge
    from veomni_tpu.resilience.elastic import ElasticRestoreError

    with pytest.raises(ElasticRestoreError, match="torn sidecar set"):
        merge_rank_states({0: _native_state(1, []), 2: _native_state(1, [])})

    # a stateful loader schema the merge does not understand (the dynamic
    # batcher's knapsack buffer) must refuse a RESIZE — silently dropping
    # the buffer would lose training samples — while a same-world split
    # (mesh-only resize) still passes the original docs through byte-exact
    dyn = _native_state(5, [])
    dyn["dataloader"]["buffer"] = {"buffer": ["sample"]}
    dyn["dataloader"]["batches_emitted"] = 3
    m_dyn = merge_rank_states({0: dyn, 1: _native_state(5, [])})
    assert split_rank_state(m_dyn, 2, 0) == dyn  # passthrough: exact
    with pytest.raises(ElasticRestoreError, match="buffer"):
        split_rank_state(m_dyn, 1, 0)

    # a nested dataset state present on only SOME ranks is torn — merging
    # just the survivors would drop the others' consumed records
    with_ds = _native_state(5, [])
    with_ds["dataloader"]["dataset"] = {"epoch": 0, "consumed": {"00": 3},
                                        "skipped": []}
    m_torn_ds = merge_rank_states({0: with_ds, 1: _native_state(5, [])})
    with pytest.raises(ElasticRestoreError, match="nested dataset state"):
        split_rank_state(m_torn_ds, 1, 0)

    # epoch skew: a rank already rolled into the next epoch had its cursor
    # RESET at rollover, so a resize cannot tell which records its old
    # block covered — merging would re-train that whole block. Refused on
    # resize; same-world passthrough stays exact.
    ahead = _native_state(2, ["z"], epoch=1)
    m_skew = merge_rank_states({0: _native_state(90, ["a"]), 1: ahead})
    assert split_rank_state(m_skew, 2, 1) == ahead
    with pytest.raises(ElasticRestoreError, match="epoch rollover"):
        split_rank_state(m_skew, 4, 0)


# ---------------------------------------------------------------------------
# streaming cursors: globally keyed, EXACT across a world resize
# ---------------------------------------------------------------------------

def _shard_corpus(tmp_path, n_shards=4, per_shard=6):
    d = tmp_path / "shards"
    d.mkdir(exist_ok=True)
    uid = 0
    for s in range(n_shards):
        with open(d / f"{s:02d}.jsonl", "w") as f:
            for _ in range(per_shard):
                f.write(json.dumps({"uid": uid}) + "\n")
                uid += 1
    return str(d), uid


def _stream(path, rank, world, **kw):
    from veomni_tpu.data.streaming import StreamingShardDataset

    return StreamingShardDataset(path, shuffle=True, seed=11, dp_rank=rank,
                                 dp_size=world, retry_base_s=0.001, **kw)


@pytest.mark.parametrize("target_world", [1, 4])
def test_streaming_elastic_resume_is_set_exact(tmp_path, target_world):
    """Mid-epoch 2-rank cursors merged and resumed on 1 and on 4 ranks: the
    union of records consumed before + after the resize is EXACTLY one epoch
    — nothing repeated, nothing skipped — because the consumed map is keyed
    by (shard, prefix-in-global-permuted-order), not by rank position."""
    from veomni_tpu.resilience.elastic import (
        merge_rank_states,
        split_rank_state,
    )

    path, total = _shard_corpus(tmp_path)
    # unequal progress: rank 0 consumed 5, rank 1 consumed 3 (ranks pack
    # different sample mixes, so equal lockstep can't be assumed)
    first = []
    states = {}
    for rank, k in ((0, 5), (1, 3)):
        ds = _stream(path, rank, 2)
        it = iter(ds)
        first += [next(it)["uid"] for _ in range(k)]
        states[rank] = {"dataloader": ds.state_dict()}
    assert len(set(first)) == len(first)

    merged = merge_rank_states(states)
    rest = []
    for r in range(target_world):
        ds = _stream(path, r, target_world)
        ds.load_state_dict(
            split_rank_state(merged, target_world, r)["dataloader"])
        rest += [row["uid"] for row in ds]  # one epoch from the cursor
    assert sorted(first + rest) == list(range(total)), (
        "elastic resume must consume exactly the records the original "
        "2-rank run had left"
    )


def test_streaming_elastic_merges_skip_history(tmp_path):
    """Poison-skip accounting survives the resize: the resumed world carries
    the full union of per-rank skip histories, so replay consumes no fresh
    budget wherever the poisoned shard lands."""
    from veomni_tpu.resilience.elastic import (
        merge_rank_states,
        split_rank_state,
    )

    path, total = _shard_corpus(tmp_path)
    # poison one record in each of two different shards
    for shard, line in (("00.jsonl", 2), ("03.jsonl", 4)):
        p = os.path.join(path, shard)
        lines = open(p).read().splitlines()
        lines[line] = "{rot"
        open(p, "w").write("\n".join(lines) + "\n")

    states = {}
    consumed = []
    for rank in (0, 1):
        ds = _stream(path, rank, 2, skip_budget=2)
        consumed += [row["uid"] for row in ds]  # full epoch, skipping poison
        states[rank] = {"dataloader": ds.state_dict()}
    all_skips = sorted(
        tuple(e) for s in states.values()
        for e in s["dataloader"]["skipped"]
    )
    assert len(all_skips) == 2  # one poison hit per rank

    merged = merge_rank_states(states)
    out = split_rank_state(merged, 1, 0)["dataloader"]
    assert sorted(tuple(e) for e in out["skipped"]) == all_skips
    # the resumed dataset replays the identical skips without new budget
    ds = _stream(path, 0, 1, skip_budget=2)
    ds.load_state_dict(out)
    epoch2 = [row["uid"] for row in ds]  # cursor was at epoch end -> epoch 2
    assert len(epoch2) == total - 2
    assert len(ds.state_dict()["skipped"]) == 2  # no fresh budget consumed


def test_streaming_record_striding_refuses_mid_epoch_merge(tmp_path):
    """Fewer shards than ranks strides RECORDS over ranks — per-shard
    consumption is no longer a prefix, so a mid-epoch world resize must
    refuse with the actionable re-shard message instead of corrupting the
    accounting. Both directions: SAVED states in the stride regime refuse
    at merge; a resize INTO the stride regime (target ranks > shard count,
    where every saved state was prefix-clean) refuses when the merged
    cursor reaches the target dataset."""
    from veomni_tpu.resilience.elastic import (
        ElasticRestoreError,
        merge_rank_states,
        split_rank_state,
    )

    path, _ = _shard_corpus(tmp_path, n_shards=1, per_shard=12)
    states = {}
    for rank in (0, 1):
        ds = _stream(path, rank, 2)
        it = iter(ds)
        next(it)
        states[rank] = {"dataloader": ds.state_dict()}
    assert states[0]["dataloader"]["stride_records"]
    merged1 = merge_rank_states(states)  # deferred: passthrough stays legal
    assert split_rank_state(merged1, 2, 1) == states[1]
    with pytest.raises(ElasticRestoreError, match="fewer shards than"):
        split_rank_state(merged1, 4, 0)

    # target-side: save on 2 ranks over 4 shards (no striding, mid-epoch),
    # resume on 8 ranks — the target would stride records, so the merged
    # consumed-prefix map is not addressable there and must be refused
    path4, _ = _shard_corpus(tmp_path, n_shards=4, per_shard=6)
    states4 = {}
    for rank in (0, 1):
        ds = _stream(path4, rank, 2)
        it = iter(ds)
        next(it)
        states4[rank] = {"dataloader": ds.state_dict()}
    merged = merge_rank_states(states4)  # saved side is prefix-clean
    target = _stream(path4, 3, 8)
    assert target._stride_records
    with pytest.raises(ElasticRestoreError, match="re-shard the corpus"):
        target.load_state_dict(split_rank_state(merged, 8, 3)["dataloader"])


# ---------------------------------------------------------------------------
# checkpointer: topology metadata + the restore gate + sidecar merge dispatch
# ---------------------------------------------------------------------------

def _mesh_state():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()).reshape(len(jax.devices())), ("fsdp",))
    sh = NamedSharding(mesh, P("fsdp"))
    return {"w": jax.device_put(jnp.arange(64, dtype=jnp.float32), sh)}


def test_manifest_records_topology_even_with_verify_off(tmp_path):
    from veomni_tpu.checkpoint import build_checkpointer
    from veomni_tpu.resilience.integrity import (
        read_manifest,
        read_topology,
        verify_manifest,
    )

    import jax

    state = _mesh_state()
    ck = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                            verify_mode="off")
    ck.save(1, state, extra_state={"global_step": 1})
    step_dir = os.path.join(ck.ckpt_dir, "global_step_1")
    topo = read_topology(step_dir)
    assert topo is not None
    assert topo["world_size"] == 1
    assert topo["mesh"] == {"fsdp": len(jax.devices())}
    assert topo["jax"]
    # off mode recorded NO digests: the generation is diagnosable but
    # UNVERIFIABLE — an empty file table must never read as verified-clean
    assert read_manifest(step_dir)["files"] == {}
    assert verify_manifest(step_dir, mode="full") is None
    ck.close()

    # digest-ful modes carry the same topology next to the CRCs
    ck2 = build_checkpointer(str(tmp_path / "ck2"), async_save=False,
                            verify_mode="size")
    ck2.save(1, state, extra_state={"global_step": 1})
    step_dir2 = os.path.join(ck2.ckpt_dir, "global_step_1")
    assert read_topology(step_dir2)["mesh"] == topo["mesh"]
    assert read_manifest(step_dir2)["files"]
    assert verify_manifest(step_dir2, mode="size").passed
    ck2.close()


def test_async_manifest_stamps_each_steps_own_sidecar_census(tmp_path):
    """The previous async step's manifest is written from inside the NEXT
    save(), which has already captured its own topology — the census must
    be the OWNING step's (a later cursor-less save must not stamp
    rank_state_files=0 onto a generation that has sidecars, which would
    defeat the all-sidecars-lost detection)."""
    from veomni_tpu.checkpoint import build_checkpointer
    from veomni_tpu.resilience.integrity import read_topology

    state = _mesh_state()
    ck = build_checkpointer(str(tmp_path / "ck"), async_save=True,
                            verify_mode="size")
    ck.save(1, state, extra_state={"global_step": 1},
            rank_state={"dataloader": None})
    ck.save(2, state, extra_state={"global_step": 2})  # no rank state
    ck.wait()
    t1 = read_topology(os.path.join(ck.ckpt_dir, "global_step_1"))
    t2 = read_topology(os.path.join(ck.ckpt_dir, "global_step_2"))
    assert t1["rank_state_files"] == 1
    assert t2["rank_state_files"] == 0
    ck.close()


def _patch_saved_world(step_dir, world):
    mpath = os.path.join(step_dir, "manifest.json")
    doc = json.load(open(mpath))
    doc["topology"]["world_size"] = world
    doc["topology"]["rank_state_files"] = world
    doc["topology"]["mesh"] = {}
    json.dump(doc, open(mpath, "w"))


def _abstract(state):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        state)


def _save_two_rank_ckpt(tmp_path, elastic=False, **kw):
    """A step-1 generation that claims world_size=2: rank 0's real sidecar
    plus a fabricated rank 1 sidecar with a different cursor."""
    from veomni_tpu.checkpoint import build_checkpointer

    state = _mesh_state()
    ck = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                            verify_mode="size", elastic=elastic, **kw)
    ck.save(1, state, extra_state={"global_step": 1},
            rank_state={"dataloader": {
                "epoch": 0, "cursor": 10, "seed": 7, "dp_rank": 0,
                "dp_size": 2,
                "collator": {"pending": ["p0"], "dropped_oversized": 0}}})
    step_dir = os.path.join(ck.ckpt_dir, "global_step_1")
    rank1 = {"dataloader": {
        "epoch": 0, "cursor": 14, "seed": 7, "dp_rank": 1, "dp_size": 2,
        "collator": {"pending": ["p1"], "dropped_oversized": 0}}}
    with open(os.path.join(step_dir, "extra_state_rank1.json"), "w") as f:
        json.dump(rank1, f)
    _patch_saved_world(step_dir, 2)
    return ck, state, step_dir


def test_world_shrink_without_elastic_fails_actionably(tmp_path):
    """The satellite bugfix: a topology mismatch must never silently restore
    partial cursor state (the pre-elastic behavior restored THIS rank's
    sidecar and dropped the other ranks' records on a shrink — and left
    grown ranks empty). With elastic off, a pinned-step load raises the
    knob-naming error."""
    from veomni_tpu.resilience.elastic import ElasticRestoreError

    ck, state, _ = _save_two_rank_ckpt(tmp_path, elastic=False)
    with pytest.raises(ElasticRestoreError, match="ckpt_elastic"):
        ck.load(_abstract(state), step=1)
    ck.close()


def test_elastic_restore_merges_sidecars_2_to_1(tmp_path):
    from veomni_tpu.checkpoint import build_checkpointer
    from veomni_tpu.observability.metrics import get_registry

    e0 = get_registry().counter("ckpt.elastic_restores").value
    ck, state, _ = _save_two_rank_ckpt(tmp_path)
    ck.close()
    ck2 = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                             verify_mode="size", elastic=True)
    restored, extra = ck2.load(_abstract(state), step=1)
    assert int(extra["global_step"]) == 1
    dl = extra["dataloader"]
    assert dl["cursor"] == 24  # 10 + 14: global epoch position preserved
    assert sorted(dl["collator"]["pending"]) == ["p0", "p1"]
    assert np.array_equal(np.asarray(restored["w"]),
                          np.asarray(state["w"]))
    assert get_registry().counter("ckpt.elastic_restores").value - e0 == 1
    ck2.close()


def test_ckpt_reshard_fault_survived_within_retry_budget(tmp_path):
    """Satellite: the resharding path drills under tier-1 like every other
    recovery path — an injected I/O fault inside the sidecar merge/split is
    retried and the elastic restore still lands."""
    from veomni_tpu.checkpoint import build_checkpointer
    from veomni_tpu.resilience.faults import configure_faults, fired_faults

    ck, state, _ = _save_two_rank_ckpt(tmp_path)
    ck.close()
    configure_faults([{"point": "ckpt.reshard", "mode": "exception",
                       "hit": 1, "times": 2}])
    ck2 = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                             verify_mode="size", elastic=True, io_retries=3,
                             retry_base_s=0.001)
    restored, extra = ck2.load(_abstract(state), step=1)
    assert extra["dataloader"]["cursor"] == 24
    assert len([a for a in fired_faults()
                if a.point == "ckpt.reshard"]) == 2
    ck2.close()

    # exhaustion: the fault keeps firing past the budget and surfaces
    configure_faults([{"point": "ckpt.reshard", "mode": "exception",
                       "times": 20}])
    ck3 = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                             verify_mode="size", elastic=True, io_retries=1,
                             retry_base_s=0.001)
    from veomni_tpu.resilience.faults import InjectedFault

    with pytest.raises(InjectedFault):
        ck3.load(_abstract(state), step=1)
    ck3.close()


def test_legacy_mid_epoch_streaming_cursor_refuses_resize():
    """A pre-elastic streaming cursor (rank-local shard_pos/rec_pos only,
    no consumed map) cannot be transferred: an empty map would silently
    restart the epoch. Same-world passthrough stays exact."""
    from veomni_tpu.resilience.elastic import (
        ElasticRestoreError,
        merge_rank_states,
        split_rank_state,
    )

    legacy = {"dataloader": {"epoch": 0, "shard_pos": 2, "rec_pos": 17,
                             "skipped": []}}
    merged = merge_rank_states({0: legacy, 1: {"dataloader": {
        "epoch": 0, "shard_pos": 1, "rec_pos": 3, "skipped": []}}})
    assert split_rank_state(merged, 2, 0) == legacy  # passthrough: exact
    with pytest.raises(ElasticRestoreError, match="before elastic keying"):
        split_rank_state(merged, 1, 0)


def test_config_error_aborts_fallback_walk(tmp_path):
    """With elastic OFF on a resized world, the restore walk must surface
    the actionable knob error instead of sliding past the newest (resized)
    generation onto a stale pre-resize one — silently losing every step in
    between would be worse than the error."""
    import jax

    from veomni_tpu.checkpoint import build_checkpointer
    from veomni_tpu.resilience.elastic import ElasticRestoreError

    state = _mesh_state()
    ck = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                            verify_mode="size")
    for step in (1, 2):
        ck.save(step, state, extra_state={"global_step": step},
                rank_state={"dataloader": {
                    "epoch": 0, "cursor": step, "seed": 7,
                    "dp_rank": 0, "dp_size": 2,
                    "collator": {"pending": [], "dropped_oversized": 0}}})
    # generation 2 claims a 2-process world; generation 1 still matches
    step2 = os.path.join(ck.ckpt_dir, "global_step_2")
    with open(os.path.join(step2, "extra_state_rank1.json"), "w") as f:
        json.dump({"dataloader": None}, f)
    _patch_saved_world(step2, 2)
    with pytest.raises(ElasticRestoreError, match="ckpt_elastic"):
        ck.load(_abstract(state))  # walk must NOT fall back to step 1
    ck.close()


def test_rotted_sidecar_is_quarantined_not_topology_refused(tmp_path):
    """Quarantine keeps precedence over the topology gate: a missing rank
    sidecar that the digest manifest condemns is storage rot — the
    generation must be quarantined (counted, renamed, walked past), not
    merely refused as an elastic incompatibility that would leave the
    rotted dir as the newest committed generation forever."""
    import jax

    from veomni_tpu.checkpoint import build_checkpointer
    from veomni_tpu.resilience import CheckpointCorruptError

    state = _mesh_state()
    ck = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                            verify_mode="size")
    ck.save(1, state, extra_state={"global_step": 1},
            rank_state={"dataloader": None})
    step_dir = os.path.join(ck.ckpt_dir, "global_step_1")
    os.remove(os.path.join(step_dir, "extra_state_rank0.json"))
    with pytest.raises(CheckpointCorruptError):
        ck.load(_abstract(state), step=1)
    assert os.path.isdir(os.path.join(ck.ckpt_dir, "global_step_1.corrupt"))
    ck.close()

    # with ckpt_verify=off there are no digests to condemn the loss, but
    # the topology's recorded sidecar count still catches it — losing ALL
    # sidecars must not classify as a cursor-less mesh resize
    from veomni_tpu.resilience.elastic import ElasticRestoreError

    ck2 = build_checkpointer(str(tmp_path / "ck2"), async_save=False,
                             verify_mode="off", elastic=True)
    ck2.save(1, state, extra_state={"global_step": 1},
             rank_state={"dataloader": None})
    os.remove(os.path.join(ck2.ckpt_dir, "global_step_1",
                           "extra_state_rank0.json"))
    with pytest.raises(ElasticRestoreError, match="torn or lost"):
        ck2.load(_abstract(state), step=1)
    ck2.close()


def test_model_parallel_degree_change_refused(tmp_path):
    from veomni_tpu.checkpoint import build_checkpointer
    from veomni_tpu.resilience.elastic import ElasticRestoreError

    state = _mesh_state()
    ck = build_checkpointer(str(tmp_path / "ck"), async_save=False,
                            verify_mode="size", elastic=True)
    ck.save(1, state, extra_state={"global_step": 1},
            rank_state={"dataloader": None})
    step_dir = os.path.join(ck.ckpt_dir, "global_step_1")
    mpath = os.path.join(step_dir, "manifest.json")
    doc = json.load(open(mpath))
    doc["topology"]["mesh"] = {"fsdp": 1, "tp": 4}  # claim a TP=4 source
    json.dump(doc, open(mpath, "w"))
    # even WITH elastic on: a TP degree change is truly incompatible
    with pytest.raises(ElasticRestoreError, match="'tp' changed"):
        ck.load(_abstract(state), step=1)
    ck.close()


# ---------------------------------------------------------------------------
# operator CLI: topology printing + ELASTIC-OK/INCOMPATIBLE verdicts
# ---------------------------------------------------------------------------

def test_verify_ckpt_cli_topology_and_elastic_verdicts(tmp_path, capsys):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import verify_ckpt

    ck, state, step_dir = _save_two_rank_ckpt(tmp_path)
    ck.close()

    # world 2 saved (complete sidecars): ELASTIC-OK for 2 (same) and 1/4
    # (resize); after removing rank 1's sidecar the resize is INCOMPATIBLE
    rc = verify_ckpt.main([str(tmp_path / "ck"), "--mode", "size",
                           "--target-world-size", "4"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "topology: world_size=2" in out
    assert "ELASTIC-OK for world_size=4" in out

    os.remove(os.path.join(step_dir, "extra_state_rank1.json"))
    rc = verify_ckpt.main([str(tmp_path / "ck"), "--mode", "size",
                           "--target-world-size", "4"])
    out = capsys.readouterr().out
    # distinct exit code: intact bytes (not 1) but a scripted pre-resize
    # gate must still fail (not 0)
    assert rc == 3
    assert "INCOMPATIBLE for world_size=4" in out
    assert "1 elastically incompatible" in out


# ---------------------------------------------------------------------------
# subprocess acceptance drills: 4-device save -> 2/8-device resume
# ---------------------------------------------------------------------------

DENSE_TOY = {
    "model_type": "qwen3", "vocab_size": 256, "hidden_size": 32,
    "intermediate_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 16,
    "qk_norm": True,
}

_DRIVER = """\
import json, os, sys

cfg = json.load(open(sys.argv[1]))
sys.path.insert(0, cfg["repo"])

from veomni_tpu.arguments import VeOmniArguments
from veomni_tpu.trainer import TextTrainer
from veomni_tpu.trainer.callbacks import Callback

args = VeOmniArguments()
args.model.config_overrides = cfg["toy"]
args.data.train_path = cfg["data"]
args.data.data_type = "pretokenized"
args.data.max_seq_len = 64
if cfg.get("dataset_type"):
    args.data.dataset_type = cfg["dataset_type"]
t = args.train
t.output_dir = cfg["out"]
t.micro_batch_size = cfg["micro_batch_size"]
t.train_steps = cfg["train_steps"]
t.save_steps = cfg.get("save_steps", 0)
t.async_save = False
t.ckpt_verify = cfg.get("ckpt_verify", "size")
t.ckpt_elastic = bool(cfg.get("ckpt_elastic", False))
t.data_skip_budget = cfg.get("data_skip_budget", 0)
# constant LR: cosine bakes train_steps into every update and the legs
# train different horizons
t.lr_decay_style = "constant"
t.lr = 1e-3
t.bf16 = False
t.save_hf_weights = False
t.log_steps = 1

trainer = TextTrainer(args)
res = {}


def state_digest(tree):
    # crc32 over each leaf's bytes on the host: what a restore must give back
    import zlib
    import jax
    import numpy as np
    return {jax.tree_util.keystr(path): zlib.crc32(np.asarray(leaf).tobytes())
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


class Rec(Callback):
    # appended after the CheckpointCallback: on_train_begin sees the state
    # auto-resume restored, before the first step; on_train_end the state
    # the train-end save wrote
    def on_train_begin(self, tr, state):
        res["digest_at_begin"] = state_digest(tr.train_state)

    def on_train_end(self, tr, state):
        res["digest_at_end"] = state_digest(tr.train_state)

    def on_step_end(self, tr, state):
        if state.synced:
            with open(cfg["loss_log"], "a") as f:
                f.write(json.dumps({
                    "step": state.global_step,
                    "loss_hex": float(state.metrics["loss"]).hex(),
                }) + "\\n")


trainer.callbacks.append(Rec())
ctl = trainer.train()
trainer.checkpointer.close()
res["global_step"] = ctl.global_step
res["elastic_restores"] = __import__(
    "veomni_tpu.observability.metrics", fromlist=["get_registry"]
).get_registry().counter("ckpt.elastic_restores").value
if hasattr(trainer.dataset, "state_dict"):
    res["dataset_state"] = trainer.dataset.state_dict()
with open(cfg["result"], "w") as f:
    json.dump(res, f)
"""

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_driver(tmp_path, cfg, ndev, extra_env=None):
    """One training leg on an ``ndev``-device virtual CPU mesh. The device
    topology is pinned per leg (not inherited from the pytest process) —
    this IS the mesh resize under test."""
    driver = tmp_path / "driver.py"
    driver.write_text(_DRIVER)
    cfg = dict(cfg, repo=_REPO, toy=DENSE_TOY)
    cfg_path = tmp_path / (os.path.basename(cfg["loss_log"]) + ".cfg.json")
    cfg_path.write_text(json.dumps(cfg))
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", VEOMNI_LOG_LEVEL="WARNING",
        XLA_FLAGS=f"{os.environ.get('XLA_FLAGS', '')} --xla_force_host_platform_device_count={ndev}",
    )
    env.pop("VEOMNI_FAULT_PLAN", None)
    if extra_env:
        env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, str(driver), str(cfg_path)],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=600,
    )
    return proc


def _cfg(tmp_path, out_name, loss_log, micro_batch_size, **over):
    cfg = {
        "data": str(tmp_path / "data.jsonl"),
        "out": str(tmp_path / out_name),
        "loss_log": str(tmp_path / loss_log),
        "result": str(tmp_path / (loss_log + ".result.json")),
        "train_steps": 8,
        "micro_batch_size": micro_batch_size,
    }
    cfg.update(over)
    return cfg


def _losses(path):
    out = {}
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            out[rec["step"]] = rec["loss_hex"]
    return out


def _write_data(path, n=96, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(n):
            f.write(json.dumps({
                "input_ids": rng.integers(0, vocab, int(rng.integers(16, 80))).tolist(),
            }) + "\n")


def test_subprocess_elastic_resume_on_smaller_and_larger_mesh(tmp_path):
    """THE acceptance drill: train + save on a 4-device mesh, resume on 2
    and on 8 devices (micro batch scaled inversely so the global batch —
    and with it the math — is constant). What is exact is the RESTORE: the
    state the resumed run holds before its first step equals, leaf for leaf
    and bit for bit, the state the 4-device run saved (a crc32 over each
    leaf's bytes on the host). The trajectory after it is held to the
    mesh-matched control at the tolerance two controls on different meshes
    are held to: XLA sums partial reductions in mesh-shaped order, so a run
    on 4 devices and one on M differ by float32 reduction-order noise from
    the first steps on, and a state one ulp from the control's at step 4
    cannot give the control's bits at step 5."""
    _write_data(tmp_path / "data.jsonl")

    ctl4 = _cfg(tmp_path, "ctl4_out", "ctl4.jsonl", 2, save_steps=2)
    proc = _run_driver(tmp_path, ctl4, ndev=4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    ref4 = _losses(ctl4["loss_log"])
    assert sorted(ref4) == list(range(1, 9))

    # leg 1: 4-device mesh, stops at step 4 with checkpoints at 2 and 4
    leg1 = _cfg(tmp_path, "elastic_out", "leg1.jsonl", 2,
                train_steps=4, save_steps=2)
    proc = _run_driver(tmp_path, leg1, ndev=4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    leg1_losses = _losses(leg1["loss_log"])
    saved = json.load(open(leg1["result"]))["digest_at_end"]
    assert len(saved) > 10  # params, both AdamW moments, the step counters

    # resume the same run on 2 devices and (separately) on 8 — each from a
    # FRESH copy of leg 1's output (a resume's own train-end save would
    # otherwise become the next leg's resume point)
    for ndev, mb, log in ((2, 4, "resume2"), (8, 1, "resume8")):
        ctl_m = _cfg(tmp_path, f"ctl{ndev}_out", f"ctl_{log}.jsonl", mb)
        proc = _run_driver(tmp_path, ctl_m, ndev=ndev)
        assert proc.returncode == 0, proc.stderr[-2000:]
        ref_m = _losses(ctl_m["loss_log"])
        # the shared prefix (steps 1-4, before the resize) and the whole
        # mesh-matched control agree with the 4-device control to f32
        # reduction-order noise — cross-mesh math equivalence
        for step in range(1, 9):
            a, b = float.fromhex(ref4[step]), float.fromhex(ref_m[step])
            assert np.isclose(a, b, rtol=1e-5, atol=0), (step, a, b)
        assert all(ref4[s] == leg1_losses[s] for s in range(1, 5))

        out_m = str(tmp_path / f"elastic_out_{ndev}")
        shutil.copytree(leg1["out"], out_m)
        leg2 = _cfg(tmp_path, f"elastic_out_{ndev}", f"{log}.jsonl", mb,
                    save_steps=0, ckpt_elastic=True)
        proc = _run_driver(tmp_path, leg2, ndev=ndev)
        assert proc.returncode == 0, (
            f"resume on {ndev} devices failed:\n" + proc.stderr[-2000:]
        )
        result = json.load(open(leg2["result"]))
        assert result["global_step"] == 8
        assert result["elastic_restores"] >= 1  # the gate saw the resize
        # bit-exact where it is true: the restore onto the resized mesh
        restored = result["digest_at_begin"]
        assert restored == saved, (
            f"{ndev}-device restore differs from the 4-device save in "
            f"{sorted(k for k in saved if restored.get(k) != saved[k])}"
        )
        got = _losses(leg2["loss_log"])
        assert sorted(got) == list(range(5, 9))  # resumed from step 4
        for step, hexloss in got.items():
            a, b = float.fromhex(hexloss), float.fromhex(ref_m[step])
            assert np.isclose(a, b, rtol=1e-5, atol=0), (
                f"{ndev}-device resume, step {step}: loss {a} against the "
                f"{ndev}-device control's {b}"
            )

    # without the knob, the mesh resize is refused with the actionable error
    noknob_out = str(tmp_path / "elastic_out_noknob")
    shutil.copytree(leg1["out"], noknob_out)
    noknob = _cfg(tmp_path, "elastic_out_noknob", "noknob.jsonl", 4,
                  save_steps=0)
    proc = _run_driver(tmp_path, noknob, ndev=2)
    assert proc.returncode != 0
    assert "ckpt_elastic" in proc.stderr


def test_subprocess_elastic_composes_with_integrity_fallback(tmp_path):
    """Satellite: elastic restore composed with PR 5 integrity — the newest
    generation rots (corrupt fault after its digests are recorded), the
    resumed run on a DIFFERENT mesh quarantines it under ckpt_verify=full,
    falls back one generation, and replays bit-exactly vs the control —
    streaming skip-budget accounting replayed identically across the
    topology change."""
    shard_dir = tmp_path / "stream_shards"
    shard_dir.mkdir()
    rng = np.random.default_rng(0)
    poison_idx = 7
    with open(shard_dir / "00.jsonl", "w") as f:
        for i in range(64):
            if i == poison_idx:
                f.write("{this is not json\n")
                continue
            f.write(json.dumps({
                "input_ids": rng.integers(
                    0, 256, int(rng.integers(16, 80))).tolist(),
            }) + "\n")

    common = dict(dataset_type="streaming", data_skip_budget=1,
                  ckpt_verify="full")
    # the bit-exact oracle shares the corrupt leg's MESH HISTORY (4-device
    # steps 1-2, elastic 2-device resume for 3-8, no corruption): both legs
    # restore the identical step-2 state, so the fallback must change
    # NOTHING about the trajectory. (A single-mesh control is only equal to
    # f32 reduction-order noise — see the mesh-resize drill above.)
    c1 = _cfg(tmp_path, "icc_out", "icc1.jsonl", 2,
              train_steps=2, save_steps=2, **common)
    c1["data"] = str(shard_dir)
    proc = _run_driver(tmp_path, c1, ndev=4)
    assert proc.returncode == 0, proc.stderr[-2000:]
    c2 = _cfg(tmp_path, "icc_out", "icc2.jsonl", 4, save_steps=0,
              ckpt_elastic=True, **common)
    c2["data"] = str(shard_dir)
    proc = _run_driver(tmp_path, c2, ndev=2)
    assert proc.returncode == 0, proc.stderr[-2000:]
    ref = {**_losses(c1["loss_log"]), **_losses(c2["loss_log"])}
    assert sorted(ref) == list(range(1, 9))
    assert json.load(open(c2["result"]))["dataset_state"]["skipped"] == [
        ["00.jsonl", poison_idx]]

    # leg 1 on 4 devices: checkpoints at 2 and 4; the ckpt.manifest corrupt
    # fault (hit 2 = the step-4 manifest) bitflips the step-4 payload AFTER
    # its digests were recorded — the storage-rot timeline
    leg1 = _cfg(tmp_path, "ivic_out", "ivic1.jsonl", 2,
                train_steps=4, save_steps=2, **common)
    leg1["data"] = str(shard_dir)
    plan = [{"point": "ckpt.manifest", "mode": "corrupt", "hit": 2,
             "op": "bitflip"}]
    proc = _run_driver(tmp_path, leg1, ndev=4,
                       extra_env={"VEOMNI_FAULT_PLAN": json.dumps(plan)})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert _losses(leg1["loss_log"])[2] == ref[2]  # shared 4-device prefix
    ck_dir = os.path.join(leg1["out"], "checkpoints")
    assert os.path.isdir(os.path.join(ck_dir, "global_step_4"))

    # leg 2 resumes on 2 devices with full verification: step 4 quarantined,
    # step 2 restored ONTO THE RESIZED MESH, steps 3-8 replayed bit-exactly
    leg2 = _cfg(tmp_path, "ivic_out", "ivic2.jsonl", 4, save_steps=0,
                ckpt_elastic=True, **common)
    leg2["data"] = str(shard_dir)
    proc = _run_driver(tmp_path, leg2, ndev=2)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.load(open(leg2["result"]))
    assert result["global_step"] == 8
    assert result["elastic_restores"] >= 1
    assert result["dataset_state"]["skipped"] == [["00.jsonl", poison_idx]]
    assert os.path.isdir(os.path.join(ck_dir, "global_step_4.corrupt"))
    assert not os.path.isdir(os.path.join(ck_dir, "global_step_4"))
    got = _losses(leg2["loss_log"])
    assert sorted(got) == list(range(3, 9))  # fell back to step 2
    for step, hexloss in got.items():
        assert ref[step] == hexloss, (
            f"step {step}: elastic post-fallback loss {hexloss} != control "
            f"{ref[step]}"
        )
