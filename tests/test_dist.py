"""Disaggregated cross-stage boundary (gigapath_tpu/dist/): protocol
units, backpressure, membership/reassignment, the per-stage sharding
registry, and the ISSUE 11 acceptance — a REAL two-process CPU run that
loses a tile worker mid-slide and still produces the clean run's slide
embedding bit-exact, with the recovery on the obs bus.
"""

import glob
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapath_tpu.dist.boundary import (
    BoundaryConfig,
    DirChannelConsumer,
    DirChannelProducer,
    EmbeddingChunk,
    MemoryChannel,
    SlideAssembler,
    assign_chunks,
    chunk_checksum,
    plan_chunks,
)
from gigapath_tpu.dist.membership import (
    Membership,
    WorkerLease,
    reassignments_for,
    write_reassignment,
)
from gigapath_tpu.obs.runlog import RunLog


def _chunk(cid, start, stop, dim=4, slide="s0", producer="w0", seed=0):
    rng = np.random.default_rng([seed, cid])
    return EmbeddingChunk.build(
        slide, cid, start, stop,
        rng.standard_normal((stop - start, dim), dtype=np.float32),
        coords=rng.uniform(0, 100, (stop - start, 2)).astype(np.float32),
        producer=producer,
    )


def _events(path):
    with open(path, encoding="utf-8") as fh:
        return [json.loads(line) for line in fh if line.strip()]


def _run_events(root):
    """Every obs event of a (multi-process) run dir, torn tails
    tolerated — a SIGKILLed process can die mid-line."""
    events = []
    for path in glob.glob(os.path.join(str(root), "obs", "*.jsonl")):
        if os.path.basename(path).startswith("flight-"):
            continue
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError:
                    continue
    return events


def _of(events, kind, **match):
    out = [ev for ev in events if ev.get("kind") == kind]
    for k, v in match.items():
        out = [ev for ev in out if ev.get(k) == v]
    return out


# ---------------------------------------------------------------------------
# chunk plan
# ---------------------------------------------------------------------------

class TestChunkPlan:
    def test_plan_covers_range_in_order(self):
        chunks = plan_chunks(50, 8)
        assert chunks[0] == (0, 0, 8)
        assert chunks[-1] == (6, 48, 50)  # ragged tail
        covered = [t for _, s, e in chunks for t in range(s, e)]
        assert covered == list(range(50))

    def test_plan_is_deterministic(self):
        assert plan_chunks(100, 16) == plan_chunks(100, 16)

    def test_plan_rejects_degenerate(self):
        with pytest.raises(ValueError):
            plan_chunks(0, 8)
        with pytest.raises(ValueError):
            plan_chunks(8, 0)

    def test_assignment_round_robin_deterministic(self):
        a = assign_chunks(range(7), ["w1", "w0"])
        # sorted workers, sorted chunks: stable however the caller orders
        assert a == {"w0": [0, 2, 4, 6], "w1": [1, 3, 5]}
        assert assign_chunks([6, 5, 4, 3, 2, 1, 0], ["w0", "w1"]) == a

    def test_reassignment_covers_exactly_the_lost_chunks(self):
        initial = assign_chunks(range(10), ["w0", "w1", "w2"])
        lost = initial["w1"]
        again = assign_chunks(lost, ["w0", "w2"])
        assert sorted(c for cs in again.values() for c in cs) == lost

    def test_assignment_requires_workers(self):
        with pytest.raises(ValueError):
            assign_chunks([0, 1], [])


# ---------------------------------------------------------------------------
# chunks + checksums
# ---------------------------------------------------------------------------

class TestChunks:
    def test_checksum_verifies_and_detects_tamper(self):
        chunk = _chunk(0, 0, 8)
        assert chunk.verify()
        chunk.payload[3, 1] += 1.0
        assert not chunk.verify()

    def test_checksum_covers_header(self):
        chunk = _chunk(2, 16, 24)
        assert chunk.checksum != chunk_checksum(
            chunk.slide_id, chunk.chunk_id, 0, 8, chunk.payload, chunk.coords
        )

    def test_build_rejects_wrong_row_count(self):
        with pytest.raises(ValueError):
            EmbeddingChunk.build("s0", 0, 0, 8,
                                 np.zeros((5, 4), np.float32))

    def test_seq_is_chunk_id(self):
        assert _chunk(7, 56, 64).seq == 7


# ---------------------------------------------------------------------------
# memory channel: credits, backpressure, dedup
# ---------------------------------------------------------------------------

class TestMemoryChannel:
    def test_producer_blocks_at_zero_credits_and_resumes_on_ack(self, tmp_path):
        """The backpressure satellite: with capacity 2, the third send
        measurably BLOCKS until the consumer acks, and the blocking
        episode lands as a schema'd ``backpressure`` event carrying
        queue depth + credits."""
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        ch = MemoryChannel(BoundaryConfig(capacity=2, poll_s=0.01),
                           runlog=log, name="test")
        sent = []

        def produce():
            for cid in range(4):
                ch.send(_chunk(cid, cid * 8, cid * 8 + 8))
                sent.append(cid)

        producer = threading.Thread(target=produce)
        producer.start()
        deadline = time.monotonic() + 5
        while len(sent) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)  # give the third send time to (wrongly) land
        assert sent == [0, 1], "producer must block at zero credits"

        first = ch.recv(timeout=1)
        ch.ack(first.seq)           # one credit back -> exactly one more send
        deadline = time.monotonic() + 5
        while len(sent) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sent == [0, 1, 2], "one ack must unblock exactly one send"

        for _ in range(3):
            chunk = ch.recv(timeout=5)
            ch.ack(chunk.seq)
        producer.join(timeout=5)
        assert not producer.is_alive()
        assert ch.stats.backpressure_events >= 1
        assert ch.stats.blocked_s > 0
        log.close()
        bp = _of(_events(log.path), "backpressure", channel="test")
        assert bp, "no backpressure event on the blocking episode"
        assert bp[0]["credits"] == 0
        assert bp[0]["capacity"] == 2
        assert bp[0]["queue_depth"] >= 2

    def test_send_timeout_raises(self):
        ch = MemoryChannel(BoundaryConfig(capacity=1, poll_s=0.01))
        ch.send(_chunk(0, 0, 8))
        with pytest.raises(TimeoutError):
            ch.send(_chunk(1, 8, 16), timeout=0.05)

    def test_duplicates_deduped_by_seq(self):
        ch = MemoryChannel(BoundaryConfig(capacity=8))
        ch.send(_chunk(0, 0, 8))
        ch.ack(0)                      # free the credit, then re-send
        ch.send(_chunk(0, 0, 8))
        assert ch.recv(timeout=1).seq == 0
        assert ch.recv(timeout=0.05) is None
        assert ch.stats.duplicates == 1

    def test_corrupt_chunk_rejected(self):
        ch = MemoryChannel(BoundaryConfig(capacity=8))
        bad = _chunk(0, 0, 8)
        bad.payload[0, 0] += 1.0       # break the checksum
        ch.send(bad)
        assert ch.recv(timeout=0.05) is None
        assert ch.stats.corrupt == 1

    def test_unacked_is_the_requeue_set(self):
        ch = MemoryChannel(BoundaryConfig(capacity=8))
        for cid in range(3):
            ch.send(_chunk(cid, cid * 8, cid * 8 + 8))
        ch.ack(1)
        assert ch.unacked_seqs() == [0, 2]

    def test_digestless_chunk_is_the_intra_process_fast_path(self):
        """``build(digest=False)`` skips the sha256 (the inference
        prefetch hot path); the in-process channel trusts it, the
        cross-process consumer must NOT."""
        ch = MemoryChannel(BoundaryConfig(capacity=8))
        chunk = EmbeddingChunk.build(
            "s0", 0, 0, 8, np.zeros((8, 4), np.float32), digest=False)
        assert chunk.checksum == ""
        ch.send(chunk)
        assert ch.recv(timeout=1).seq == 0
        assert ch.stats.corrupt == 0

    def test_retrying_a_timed_out_send_is_one_backpressure_episode(
            self, tmp_path):
        """The worker's lease-renewing retry loop re-enters send for
        the SAME seq after each timeout; that is one blocking episode,
        not one event per retry."""
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        ch = MemoryChannel(BoundaryConfig(capacity=1, poll_s=0.005),
                           runlog=log, name="retry")
        ch.send(_chunk(0, 0, 8))
        blocked = _chunk(1, 8, 16)
        for _ in range(3):
            with pytest.raises(TimeoutError):
                ch.send(blocked, timeout=0.02)
        assert ch.stats.backpressure_events == 1
        log.close()
        assert len(_of(_events(log.path), "backpressure")) == 1


# ---------------------------------------------------------------------------
# directory channel: cross-process protocol on one process
# ---------------------------------------------------------------------------

class TestDirChannel:
    def test_roundtrip_out_of_order_and_ack_credits(self, tmp_path):
        root = str(tmp_path)
        cfg = BoundaryConfig(capacity=8, poll_s=0.005)
        prod = DirChannelProducer(root, cfg, producer="w0")
        cons = DirChannelConsumer(root, cfg)
        for cid in (2, 0, 1):          # out of order on purpose
            prod.send(_chunk(cid, cid * 8, cid * 8 + 8))
        assert prod.credits() == 5
        got = {}
        for _ in range(3):
            chunk = cons.recv(timeout=2)
            assert chunk is not None and chunk.verify()
            cons.ack(chunk.seq)
            got[chunk.seq] = chunk
        assert sorted(got) == [0, 1, 2]
        assert prod.credits() == 8     # acks refunded every credit
        assert prod.unacked_seqs() == []

    def test_retransmit_heals_a_dropped_write(self, tmp_path):
        from gigapath_tpu.resilience.chaos import ChaosInjector

        root = str(tmp_path)
        cfg = BoundaryConfig(capacity=8, poll_s=0.005, retransmit_s=0.05)
        chaos = ChaosInjector("drop_chunk@0")
        prod = DirChannelProducer(root, cfg, producer="w0", chaos=chaos)
        cons = DirChannelConsumer(root, cfg)
        prod.send(_chunk(0, 0, 8))
        assert prod.stats.dropped == 1
        assert cons.recv(timeout=0.1) is None, "the drop must actually drop"
        time.sleep(0.06)
        assert prod.pump_retransmits() == 1
        chunk = cons.recv(timeout=2)
        assert chunk is not None and chunk.seq == 0
        assert prod.stats.retransmits == 1

    def test_dup_chunk_deduped(self, tmp_path):
        from gigapath_tpu.resilience.chaos import ChaosInjector

        root = str(tmp_path)
        cfg = BoundaryConfig(capacity=8, poll_s=0.005)
        chaos = ChaosInjector("dup_chunk@1")
        prod = DirChannelProducer(root, cfg, producer="w0", chaos=chaos)
        cons = DirChannelConsumer(root, cfg)
        prod.send(_chunk(1, 8, 16))
        first = cons.recv(timeout=2)
        assert first is not None and first.seq == 1
        assert cons.recv(timeout=0.1) is None
        assert cons.stats.duplicates == 1

    def test_dir_consumer_rejects_digestless_chunks(self, tmp_path):
        """Cross-process transports must digest: an empty checksum is
        treated as corrupt, never assembled."""
        root = str(tmp_path)
        cfg = BoundaryConfig(capacity=8, poll_s=0.005)
        prod = DirChannelProducer(root, cfg, producer="w0")
        cons = DirChannelConsumer(root, cfg)
        prod.send(EmbeddingChunk.build(
            "s0", 0, 0, 8, np.zeros((8, 4), np.float32), digest=False))
        assert cons.recv(timeout=0.1) is None
        assert cons.stats.corrupt == 1

    def test_seeded_watermark_retransmit_is_reacked(self, tmp_path):
        """A restarted consumer's watermark may cover a seq whose
        deferred ack died with the predecessor (crash between
        checkpoint commit and ack flush): the retransmit must be
        swallowed AND re-acked, or the producer's credit is pinned
        forever."""
        root = str(tmp_path)
        cfg = BoundaryConfig(capacity=2, poll_s=0.005, retransmit_s=0.05)
        prod = DirChannelProducer(root, cfg, producer="w0")
        prod.send(_chunk(0, 0, 8))
        # the predecessor consumer delivered + checkpointed seq 0 but
        # died before the ack flush; the restart seeds the watermark
        cons = DirChannelConsumer(root, cfg, delivered=[0])
        time.sleep(0.06)
        assert prod.pump_retransmits() == 1
        assert cons.recv(timeout=0.1) is None     # deduped, not re-folded
        assert cons.stats.duplicates >= 1
        assert prod.credits() == 2, "the swallowed retransmit must re-ack"
        assert prod.unacked_seqs() == []

    def test_deferred_ack_duplicate_is_not_reacked(self, tmp_path):
        """The inverse guard: a retransmit duplicate of a chunk whose
        ack is still DEFERRED (delivered this session, not yet covered
        by a checkpoint) must NOT be acked — an ack is a durability
        promise, and acking here would let a crash strand the chunk
        forever (found by end-to-end verification: the predecessor
        consumer deduped a retransmit of an uncheckpointed chunk, acked
        it, died, and the slide could never complete)."""
        root = str(tmp_path)
        cfg = BoundaryConfig(capacity=2, poll_s=0.005, retransmit_s=0.05)
        prod = DirChannelProducer(root, cfg, producer="w0")
        cons = DirChannelConsumer(root, cfg)
        prod.send(_chunk(0, 0, 8))
        assert cons.recv(timeout=1).seq == 0   # delivered, ack DEFERRED
        time.sleep(0.06)
        assert prod.pump_retransmits() == 1
        assert cons.recv(timeout=0.1) is None  # deduped
        assert cons.stats.duplicates >= 1
        assert prod.credits() == 1, (
            "a deferred-ack duplicate must not refund the credit"
        )
        cons.ack(0)                            # the checkpoint commits
        assert prod.credits() == 2

    def test_backpressure_event_from_dir_producer(self, tmp_path):
        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        cfg = BoundaryConfig(capacity=1, poll_s=0.005)
        prod = DirChannelProducer(str(tmp_path), cfg, producer="w0",
                                  runlog=log)
        prod.send(_chunk(0, 0, 8))
        with pytest.raises(TimeoutError):
            prod.send(_chunk(1, 8, 16), timeout=0.05)
        log.close()
        bp = _of(_events(log.path), "backpressure")
        assert bp and bp[0]["credits"] == 0 and bp[0]["capacity"] == 1


# ---------------------------------------------------------------------------
# assembly
# ---------------------------------------------------------------------------

class TestAssembler:
    def test_out_of_order_assembly_is_exact(self):
        chunks = [_chunk(cid, cid * 8, cid * 8 + 8, dim=4)
                  for cid in range(4)]
        direct = np.concatenate([c.payload for c in chunks])
        asm = SlideAssembler(32, 4)
        asm.expect(range(4))
        for c in (chunks[3], chunks[0], chunks[2], chunks[1]):
            assert asm.add(c)
        assert asm.complete()
        np.testing.assert_array_equal(asm.embeds, direct)

    def test_duplicate_add_ignored_and_missing_tracked(self):
        asm = SlideAssembler(16, 4)
        asm.expect([0, 1])
        c = _chunk(0, 0, 8)
        assert asm.add(c)
        assert not asm.add(c)
        assert asm.missing() == [1]
        assert not asm.complete()


# ---------------------------------------------------------------------------
# membership + reassignment
# ---------------------------------------------------------------------------

class TestMembership:
    def test_renew_keeps_alive_expiry_reports_once(self, tmp_path):
        root = str(tmp_path)
        log = RunLog(os.path.join(root, "run.jsonl"), driver="t", echo=False)
        lease = WorkerLease(root, "w0", lease_s=10.0)
        lease.register(now=100.0)
        m = Membership(root, runlog=log)
        assert m.alive(now=105.0) == ["w0"]
        assert m.poll_lost(now=105.0) == []
        # renew pushes expiry out
        lease.renew(now=109.0)
        assert m.alive(now=115.0) == ["w0"]
        # silence past expiry -> lost, exactly once
        assert m.poll_lost(now=130.0) == ["w0"]
        assert m.poll_lost(now=131.0) == []
        assert m.lost() == ["w0"]
        log.close()
        lost = _of(_events(log.path), "worker_lost", worker="w0")
        assert len(lost) == 1
        assert lost[0]["stage"] == "tile"
        assert lost[0]["expired_by_s"] > 0

    def test_renew_is_rate_limited(self, tmp_path):
        lease = WorkerLease(str(tmp_path), "w0", lease_s=9.0)
        lease.register(now=100.0)
        assert not lease.renew(now=101.0)   # < lease/3 elapsed
        assert lease.renew(now=103.1)

    def test_retire_removes_the_lease(self, tmp_path):
        root = str(tmp_path)
        lease = WorkerLease(root, "w0", lease_s=10.0)
        lease.register(now=100.0)
        lease.retire()
        assert Membership(root).alive(now=100.1) == []

    def test_reassignment_roundtrip_and_recovery_event(self, tmp_path):
        root = str(tmp_path)
        log = RunLog(os.path.join(root, "run.jsonl"), driver="t", echo=False)
        write_reassignment(root, lost_worker="w0",
                           assignments={"w1": [4, 2], "w2": [6]},
                           runlog=log)
        seen: set = set()
        assert reassignments_for(root, "w1", seen) == [2, 4]
        assert reassignments_for(root, "w1", seen) == []  # once per file
        assert reassignments_for(root, "w2") == [6]
        log.close()
        rec = _of(_events(log.path), "recovery", action="reassign")
        assert rec and rec[0]["worker"] == "w0" and rec[0]["chunks"] == 3
        assert rec[0]["survivors"] == ["w1", "w2"]

    def test_report_lost_is_direct_evidence_once(self, tmp_path):
        """The orchestrator's process-exit probe marks a worker lost
        without any lease (startup deaths have none); once per worker,
        and the lease path never double-reports it."""
        root = str(tmp_path)
        log = RunLog(os.path.join(root, "run.jsonl"), driver="t", echo=False)
        m = Membership(root, runlog=log)
        assert m.report_lost("w9", reason="process_exit", exit_code=-9)
        assert not m.report_lost("w9", reason="process_exit", exit_code=-9)
        assert m.lost() == ["w9"]
        log.close()
        lost = _of(_events(log.path), "worker_lost", worker="w9")
        assert len(lost) == 1 and lost[0]["reason"] == "process_exit"

    def test_crashed_worker_leaves_its_lease_clean_exit_retires(
            self, tmp_path):
        """A worker that does NOT exit cleanly must leave its lease to
        expire (that is how a lease-only coordinator learns of the
        death); a clean exit retires it."""
        from gigapath_tpu.dist.worker import run_tile_worker, write_plan
        from gigapath_tpu.dist.pipeline import default_plan

        root = str(tmp_path)
        plan = default_plan(n_tiles=8, chunk_tiles=8, lease_s=30.0,
                            workers=["w0"])
        write_plan(root, plan)
        # deadline 0: the loop never runs, status='deadline' (not ok)
        run_tile_worker(root, "w0", deadline_s=0.0)
        assert Membership(root).alive() == ["w0"], (
            "a non-clean exit must NOT retire the lease"
        )
        # clean exit: DONE pre-published, worker drains and retires
        from gigapath_tpu.dist.worker import DONE_MARKER

        with open(os.path.join(root, DONE_MARKER), "w"):
            pass
        run_tile_worker(root, "w0", deadline_s=30.0)
        assert Membership(root).alive() == []

    def test_credit_blocked_worker_drains_on_done(self, tmp_path):
        """A worker stuck at zero credits (nobody acking) must drain
        out the moment DONE is published — not spin to its own
        deadline."""
        from gigapath_tpu.dist.pipeline import default_plan
        from gigapath_tpu.dist.worker import (
            DONE_MARKER,
            run_tile_worker,
            write_plan,
        )

        root = str(tmp_path)
        plan = default_plan(n_tiles=16, chunk_tiles=8, lease_s=0.4,
                            credits=1, workers=["w0"])
        write_plan(root, plan)
        with open(os.path.join(root, DONE_MARKER), "w"):
            pass
        t0 = time.monotonic()
        stats = run_tile_worker(root, "w0", deadline_s=30.0)
        wall = time.monotonic() - t0
        assert stats["status"] == "ok"       # orderly drain, not failure
        assert stats["sent"] == 1            # second chunk never acked
        assert wall < 5, f"drain took {wall:.1f}s — spun past DONE"

    def test_anomaly_engine_reacts_to_worker_lost(self, tmp_path):
        from gigapath_tpu.obs.anomaly import AnomalyConfig, attach_anomaly_engine

        log = RunLog(str(tmp_path / "run.jsonl"), driver="t", echo=False)
        engine = attach_anomaly_engine(
            log, config=AnomalyConfig(capture_budget=0))
        log.event("worker_lost", worker="w3", stage="tile",
                  expired_by_s=0.5)
        log.close()
        fired = [a for a in engine.anomalies
                 if a.get("detector") == "worker_lost"]
        assert fired and fired[0]["worker"] == "w3"
        assert fired[0]["flight"], "worker_lost must dump flight context"


# ---------------------------------------------------------------------------
# chaos parsing
# ---------------------------------------------------------------------------

class TestDistChaos:
    def test_new_injectors_parse(self):
        from gigapath_tpu.resilience.chaos import ChaosInjector

        c = ChaosInjector("kill_worker@3,slow_worker@2:0.5,drop_chunk@1,"
                          "dup_chunk@4")
        assert c._kill_worker_after == 3
        assert c.slow_worker(2) == 0.5 and c.slow_worker(0) == 0.0
        assert c.drops_chunk(1) and not c.drops_chunk(1)  # one-shot
        assert c.dups_chunk(4) and not c.dups_chunk(4)

    def test_slow_worker_star_slows_every_chunk(self):
        from gigapath_tpu.resilience.chaos import ChaosInjector

        c = ChaosInjector("slow_worker@*:0.2")
        assert c.slow_worker(0) == 0.2 and c.slow_worker(99) == 0.2

    def test_null_chaos_has_the_surface(self):
        from gigapath_tpu.resilience.chaos import NullChaos

        n = NullChaos()
        assert not n.maybe_kill_worker(5)
        assert n.slow_worker(0) == 0.0
        assert not n.drops_chunk(0) and not n.dups_chunk(0)

    def test_unknown_injector_still_raises(self):
        from gigapath_tpu.resilience.chaos import ChaosInjector

        with pytest.raises(ValueError):
            ChaosInjector("explode_worker@1")


# ---------------------------------------------------------------------------
# stage meshes + the sharding-rule registry
# ---------------------------------------------------------------------------

class TestStageMesh:
    def test_match_partition_rules_first_match_wins(self):
        from jax.sharding import PartitionSpec as P

        from gigapath_tpu.dist.stagemesh import match_partition_rules

        params = {
            "layer": {"fc1": {"kernel": np.zeros((4, 8))},
                      "fc2": {"kernel": np.zeros((8, 4)),
                              "bias": np.zeros((4,))}},
            "scale": np.ones(()),
        }
        specs = match_partition_rules(
            (
                (r"fc1/kernel$", P(None, "model")),
                (r"fc2/kernel$", P("model", None)),
                (r".*", P()),
            ),
            params,
        )
        assert specs["layer"]["fc1"]["kernel"] == P(None, "model")
        assert specs["layer"]["fc2"]["kernel"] == P("model", None)
        assert specs["layer"]["fc2"]["bias"] == P()
        # scalars never partition, whatever the rules say
        assert specs["scale"] == P()

    def test_uncovered_param_is_a_loud_error(self):
        from jax.sharding import PartitionSpec as P

        from gigapath_tpu.dist.stagemesh import match_partition_rules

        with pytest.raises(ValueError, match="no partition rule"):
            match_partition_rules(
                ((r"fc1/kernel$", P()),),
                {"other": {"kernel": np.zeros((4, 4))}},
            )

    def test_registry_has_both_stages(self):
        from gigapath_tpu.dist.stagemesh import get_stage, stage_names

        assert stage_names() == ["slide_encoder", "tile_encoder"]
        assert get_stage("tile_encoder").axes == ("data", "model")
        assert get_stage("slide_encoder").axes == ("data", "seq", "model")
        with pytest.raises(KeyError):
            get_stage("nope")

    def test_stage_mesh_axes_and_device_subset(self):
        from gigapath_tpu.dist.stagemesh import stage_mesh

        devices = jax.devices()
        tile = stage_mesh("tile_encoder", devices=devices[:4])
        assert tile.axis_names == ("data", "model")
        assert tile.devices.size == 4
        slide = stage_mesh("slide_encoder", devices=devices[4:])
        assert slide.axis_names == ("data", "seq", "model")
        assert {d.id for d in tile.devices.flat}.isdisjoint(
            {d.id for d in slide.devices.flat}
        )

    def test_stage_param_shardings_cover_a_real_model(self):
        from gigapath_tpu.dist.stagemesh import (
            stage_mesh,
            stage_param_shardings,
        )
        from gigapath_tpu.models.classification_head import get_model

        _, params = get_model(
            input_dim=16, latent_dim=32, feat_layer="1", n_classes=2,
            model_arch="gigapath_slide_enc_tiny", dtype=None,
        )
        mesh = stage_mesh("slide_encoder", devices=jax.devices()[:8],
                          axis_sizes={"data": 1, "seq": 4, "model": 2})
        shardings = stage_param_shardings("slide_encoder", params, mesh)
        leaves = jax.tree_util.tree_leaves(
            shardings, is_leaf=lambda x: hasattr(x, "spec"))
        assert leaves and all(hasattr(s, "spec") for s in leaves)
        # at least one kernel actually tensor-parallel under the rules
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        specs = jax.tree_util.tree_flatten_with_path(
            jax.tree_util.tree_map(lambda s: s, shardings,
                                   is_leaf=lambda x: hasattr(x, "spec")))[0]
        split = [s for (_, s) in specs if any(e is not None for e in s.spec)]
        assert split, "no parameter picked up a model-parallel rule"

    def test_degrade_drops_missing_axes(self):
        from gigapath_tpu.dist.stagemesh import (
            stage_mesh,
            stage_param_shardings,
        )

        params = {"fc1": {"kernel": np.zeros((4, 8), np.float32)}}
        mesh = stage_mesh("tile_encoder", devices=jax.devices()[:1])
        shardings = stage_param_shardings("tile_encoder", params, mesh)
        # a 1-device mesh has no live axes: everything degrades to P()
        assert all(not any(e is not None for e in s.spec)
                   for s in jax.tree_util.tree_leaves(
                       shardings, is_leaf=lambda x: hasattr(x, "spec")))


# ---------------------------------------------------------------------------
# zero retraces: channel on vs off
# ---------------------------------------------------------------------------

class TestChannelRetraceParity:
    def test_channel_fed_forward_compiles_once(self):
        """The boundary moves numpy on the host; feeding a jitted
        forward through it must hit the SAME jit cache entry as feeding
        it directly — zero extra compiles with the channel on."""

        @jax.jit
        def forward(x):
            return jnp.tanh(x).sum(axis=0)

        chunks = [_chunk(cid, cid * 8, cid * 8 + 8, dim=4)
                  for cid in range(4)]
        direct = np.concatenate([c.payload for c in chunks])
        out_direct = np.asarray(forward(direct))
        assert forward._cache_size() == 1

        ch = MemoryChannel(BoundaryConfig(capacity=8))
        for c in chunks:
            ch.send(c)
        asm = SlideAssembler(32, 4)
        asm.expect(range(4))
        while not asm.complete():
            chunk = ch.recv(timeout=1)
            asm.add(chunk)
            ch.ack(chunk.seq)
        out_channel = np.asarray(forward(asm.embeds))
        assert forward._cache_size() == 1, "the channel caused a retrace"
        np.testing.assert_array_equal(out_direct, out_channel)


# ---------------------------------------------------------------------------
# inference prefetch wiring
# ---------------------------------------------------------------------------

class TestInferencePrefetch:
    def _fixture(self, tmp_path, n=5):
        from gigapath_tpu.utils.checkpoint import save_checkpoint

        rng = np.random.default_rng(0)
        feature_dir = tmp_path / "features"
        for i in range(n):
            save_checkpoint(
                str(feature_dir / f"s{i}_features"),
                {"features": rng.normal(size=(8 + i, 16)).astype(np.float32),
                 "coords": rng.uniform(0, 100, (8 + i, 2)).astype(np.float32)},
            )
        return str(feature_dir)

    def test_stream_matches_synchronous_loads(self, tmp_path):
        from gigapath_tpu.inference import _feature_stream, _load_features

        feature_dir = self._fixture(tmp_path)
        files = sorted(glob.glob(os.path.join(feature_dir, "*_features.pt")))
        if not files:  # orbax feature dirs, not .pt files
            files = sorted(
                os.path.join(feature_dir, d)
                for d in os.listdir(feature_dir)
            )
        plain = [(i, p, *_load_features(p)) for i, p in enumerate(files)]
        streamed = list(_feature_stream(files, prefetch=2, runlog=None))
        assert [s[0] for s in streamed] == [p[0] for p in plain]
        for (pi, pp, pf, pc), (si, sp, sf, sc) in zip(plain, streamed):
            assert pp == sp
            np.testing.assert_array_equal(
                np.asarray(pf, np.float32), sf)
            np.testing.assert_array_equal(
                np.asarray(pc, np.float32), sc)

    def test_loader_failure_propagates(self, tmp_path):
        from gigapath_tpu.inference import _feature_stream

        with pytest.raises(Exception):
            list(_feature_stream(
                [str(tmp_path / "missing_features.pt")], prefetch=2,
                runlog=None,
            ))


# ---------------------------------------------------------------------------
# THE acceptance: two process groups, one killed mid-slide, bit-exact
# ---------------------------------------------------------------------------

class TestKillWorkerAcceptance:
    def test_kill_worker_recovery_is_bit_exact(self, tmp_path):
        """ISSUE 11 acceptance: a real two-process CPU run loses a tile
        worker mid-slide (SIGKILL via ``kill_worker@1``); the survivors
        reassign the lost tile range and the final slide embedding is
        bit-exact vs the uninterrupted run, with ``worker_lost`` +
        ``recovery action="reassign"`` on the bus and zero unexpected
        retraces."""
        from gigapath_tpu.dist.pipeline import default_plan, run_disaggregated

        # lease 1.5s: workers renew every 0.5s, so only a genuinely dead
        # worker expires, even on a loaded CI box; recovery latency in
        # the chaos half is bounded by this same window
        plan = default_plan(n_tiles=40, chunk_tiles=8, lease_s=1.5,
                            credits=4, retransmit_s=0.5)
        clean = run_disaggregated(str(tmp_path / "clean"), plan=plan,
                                  deadline_s=90)
        assert clean["lost"] == [] and clean["reassignments"] == 0
        assert all(rc == 0 for rc in clean["worker_exit_codes"].values())

        chaos = run_disaggregated(
            str(tmp_path / "chaos"), plan=plan,
            worker_chaos={"w0": "kill_worker@1"}, deadline_s=90,
        )
        assert chaos["worker_exit_codes"]["w0"] == -9, (
            f"w0 survived: {chaos['worker_exit_codes']}"
        )
        assert chaos["lost"] == ["w0"]
        assert chaos["reassignments"] >= 1

        # bit-parity: the assembled sequence AND the slide embedding
        np.testing.assert_array_equal(clean["assembled"],
                                      chaos["assembled"])
        np.testing.assert_array_equal(clean["embedding"],
                                      chaos["embedding"])

        events = _run_events(tmp_path / "chaos")
        assert _of(events, "worker_lost", worker="w0")
        reassigns = _of(events, "recovery", action="reassign")
        assert reassigns and reassigns[0]["worker"] == "w0"
        assert reassigns[0]["chunks"] >= 1
        assert _of(events, "anomaly", detector="worker_lost")
        unexpected = [ev for ev in _of(events, "compile")
                      if ev.get("unexpected")]
        assert not unexpected, unexpected


# ---------------------------------------------------------------------------
# ISSUE 13 acceptance (a): the TCP transport under frame chaos
# ---------------------------------------------------------------------------

class TestTcpBoundaryAcceptance:
    def test_tcp_chaos_run_is_bit_exact_vs_memory_channel_oracle(
            self, tmp_path):
        """ISSUE 13 acceptance (a): a REAL two-process run joined by
        the TCP transport, under ``drop_conn`` (torn frame + dead
        connection) and ``corrupt_frame`` (flipped body bytes) chaos,
        produces a slide embedding BIT-exact vs a clean in-process
        MemoryChannel oracle — with the frame errors counted, a
        ``reconnect`` recovery event on the bus, and zero unexpected
        retraces."""
        from gigapath_tpu.dist.boundary import (
            BoundaryConfig,
            MemoryChannel,
            SlideAssembler,
        )
        from gigapath_tpu.dist.pipeline import (
            _default_forward,
            default_plan,
            run_disaggregated,
        )
        from gigapath_tpu.dist.worker import encode_chunk, encoder_weights

        plan = default_plan(n_tiles=40, chunk_tiles=8, lease_s=1.5,
                            credits=4, retransmit_s=0.5, transport="tcp")

        # the clean MemoryChannel oracle: same chunks, in process,
        # through the third transport of the same protocol
        weights = encoder_weights(plan)
        channel = MemoryChannel(BoundaryConfig(capacity=8))
        chunks = plan_chunks(plan["n_tiles"], plan["chunk_tiles"])
        for cid, start, stop in chunks:
            embeds, coords = encode_chunk(plan, weights, start, stop)
            channel.send(EmbeddingChunk.build(
                plan["slide_id"], cid, start, stop, embeds, coords=coords,
            ))
        asm = SlideAssembler(plan["n_tiles"], plan["dim_out"])
        asm.expect([c[0] for c in chunks])
        while not asm.complete():
            chunk = channel.recv(timeout=1)
            asm.add(chunk)
            channel.ack(chunk.seq)
        forward, params = _default_forward()(plan["dim_out"])
        oracle = np.asarray(
            forward(params, asm.embeds[None], asm.coords[None]), np.float32
        )[0]

        chaos = run_disaggregated(
            str(tmp_path / "tcp-chaos"), plan=plan,
            worker_chaos={"w0": "drop_conn@1,corrupt_frame@2"},
            deadline_s=90,
        )
        np.testing.assert_array_equal(chaos["embedding"], oracle)
        np.testing.assert_array_equal(chaos["assembled"], asm.embeds)
        assert chaos["stats"]["frame_errors"] >= 1, chaos["stats"]
        assert chaos["lost"] == [], "frame chaos must not read as death"

        events = _run_events(tmp_path / "tcp-chaos")
        assert _of(events, "recovery", action="reconnect"), (
            "drop_conn must surface as a reconnect recovery event"
        )
        unexpected = [ev for ev in _of(events, "compile")
                      if ev.get("unexpected")]
        assert not unexpected, unexpected


# ---------------------------------------------------------------------------
# ISSUE 13 acceptance (b): consumer SIGKILL + checkpoint resume
# ---------------------------------------------------------------------------

class TestConsumerKillAcceptance:
    def test_consumer_sigkill_resumes_from_watermark_bit_exact(
            self, tmp_path):
        """ISSUE 13 acceptance (b): the slide consumer (own OS process,
        streaming fold, TCP transport, checkpoint cadence 2) is
        SIGKILLed mid-slide; the restarted consumer finds the
        checkpoint, reloads the watermark, re-handshakes, receives only
        post-watermark chunks, and produces a BIT-exact embedding — with
        ``consumer_lost`` + ``recovery action="consumer_resume"`` on the
        bus and zero unexpected retraces."""
        from gigapath_tpu.dist.pipeline import default_plan, run_disaggregated

        plan = default_plan(n_tiles=40, chunk_tiles=8, lease_s=2.0,
                            credits=4, retransmit_s=0.5,
                            chunked_prefill=True, transport="tcp",
                            consumer_ckpt_every=2)
        clean = run_disaggregated(str(tmp_path / "clean"), plan=plan,
                                  deadline_s=90)
        assert clean["streaming"]

        chaos = run_disaggregated(
            str(tmp_path / "kill"), plan=plan,
            consumer_chaos="kill_consumer@3", deadline_s=90,
        )
        exits = chaos["consumer_exit_codes"]
        assert exits[0] == -9, f"consumer was not SIGKILLed: {exits}"
        assert exits[-1] == 0, f"restarted consumer failed: {exits}"
        np.testing.assert_array_equal(clean["embedding"],
                                      chaos["embedding"])

        events = _run_events(tmp_path / "kill")
        lost = _of(events, "consumer_lost")
        assert lost and lost[0].get("reason") == "checkpoint_found"
        resumes = _of(events, "recovery", action="consumer_resume")
        assert resumes and resumes[0].get("chunks", 0) >= 1, resumes
        assert _of(events, "anomaly", detector="consumer_lost"), (
            "the anomaly engine did not react to consumer_lost"
        )
        unexpected = [ev for ev in _of(events, "compile")
                      if ev.get("unexpected")]
        assert not unexpected, unexpected


# ---------------------------------------------------------------------------
# ISSUE 12: the consumer folds chunks on arrival (streaming prefill)
# ---------------------------------------------------------------------------

class TestStreamingConsumer:
    def test_streaming_fold_on_arrival_kill_recover_bit_exact(self, tmp_path):
        """ISSUE 12 acceptance (dist leg): with ``chunked_prefill`` in
        the plan the consumer folds every acked ``EmbeddingChunk``
        straight into the streaming slide-encoder session — no dense
        ``[n_tiles, D]`` assembly — and a SIGKILLed worker's
        reassignment (out-of-order, retransmitted delivery included)
        leaves the slide embedding BIT-exact vs the clean streaming run,
        which itself matches the dense consumer at streaming tolerance."""
        from gigapath_tpu.dist.pipeline import default_plan, run_disaggregated

        plan = default_plan(n_tiles=40, chunk_tiles=8, lease_s=1.5,
                            credits=4, retransmit_s=0.5)
        dense = run_disaggregated(str(tmp_path / "dense"), plan=plan,
                                  deadline_s=90)

        stream_plan = dict(plan, chunked_prefill=True)
        clean = run_disaggregated(str(tmp_path / "clean"), plan=stream_plan,
                                  deadline_s=90)
        assert clean["streaming"] and clean["assembled"] is None
        assert clean["lost"] == [] and clean["reassignments"] == 0
        np.testing.assert_allclose(clean["embedding"], dense["embedding"],
                                   atol=1e-5, rtol=0)

        chaos = run_disaggregated(
            str(tmp_path / "chaos"), plan=stream_plan,
            worker_chaos={"w0": "kill_worker@1"}, deadline_s=90,
        )
        assert chaos["worker_exit_codes"]["w0"] == -9
        assert chaos["lost"] == ["w0"] and chaos["reassignments"] >= 1
        np.testing.assert_array_equal(clean["embedding"],
                                      chaos["embedding"])

        events = _run_events(tmp_path / "clean")
        assert _of(events, "stream_open")
        assert _of(events, "stream_finalize")


def test_stage_process_devices_names_the_cause(monkeypatch):
    """A stage process that cannot get a device fails with an error that
    says what the two-process layout needs — not with whatever the backend
    raised, swallowed by a DEVNULL stderr."""
    from gigapath_tpu.dist import stagemesh

    def in_use(*a, **k):
        raise RuntimeError("TPU is already in use by another process")

    monkeypatch.setattr(jax, "devices", in_use)
    with pytest.raises(RuntimeError, match="needs a device per process") as err:
        stagemesh.stage_process_devices()
    assert "already in use" in str(err.value)
    monkeypatch.undo()
    assert stagemesh.stage_process_devices() == jax.devices()[:1]

