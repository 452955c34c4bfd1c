"""Cluster control plane (apex_tpu.cluster) — the ISSUE-11 suite.

Generation-fenced membership, coordinated multi-rank recovery, and the
relaunch hygiene that ties them into ``elastic_run``: lease lifecycle,
monotone generation commits with CAS semantics, the checkpoint-layer
fence (write/commit/delete all refused for stale tokens), the in-use
marker that stops ``gc_checkpoints`` deleting under a concurrent
restore, signed-intent coordination with deterministic oldest-good-step
resolution, the collective-deadline watchdog tier, generation-scoped
heartbeats/straggler detection, the cluster event schema (+negative
twins), the bench backend-init guard — and the two multi-process
acceptance runs: the SIGSTOP zombie whose late commit the fence
refuses, and the coordinated rewind that resumes bitwise vs a
fault-free oracle with exactly one generation bump.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import ckpt, cluster, guard, monitor, trace
from apex_tpu.ckpt import format as _format
from apex_tpu.trace import straggler as _straggler

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from scripts.check_metrics_schema import check_cluster_lines  # noqa: E402


def _collect():
    """An event sink capturing into a list."""
    events = []
    return events, events.append


# --- generation ---------------------------------------------------------------

class TestGeneration:
    def test_fresh_directory_is_generation_zero(self, tmp_path):
        d = str(tmp_path)
        assert cluster.read_generation(d) == 0
        assert cluster.read_generation_record(d) == {"generation": 0}

    def test_bump_is_monotone_and_recorded(self, tmp_path):
        d = str(tmp_path)
        assert cluster.bump_generation(d, rank=3, reason="test") == 1
        rec = cluster.read_generation_record(d)
        assert rec["generation"] == 1
        assert rec["prev_generation"] == 0
        assert rec["committed_by_rank"] == 3
        assert rec["reason"] == "test"
        assert cluster.bump_generation(d) == 2
        assert cluster.read_generation(d) == 2

    def test_bump_expect_cas_refuses_the_lost_race(self, tmp_path):
        d = str(tmp_path)
        cluster.bump_generation(d)                      # now at 1
        with pytest.raises(cluster.StaleGenerationError) as ei:
            cluster.bump_generation(d, expect=0)        # raced & lost
        assert ei.value.generation == 0
        assert ei.value.current == 1
        # the losing racer did NOT stack an epoch
        assert cluster.read_generation(d) == 1
        # a matching expect commits
        assert cluster.bump_generation(d, expect=1) == 2

    def test_epoch_filename_is_authoritative_over_torn_content(
            self, tmp_path):
        d = str(tmp_path)
        # a stray non-epoch file is ignored entirely
        with open(os.path.join(d, "generation.notanepoch.json"),
                  "w") as f:
            f.write("{torn")
        assert cluster.read_generation(d) == 0
        # an epoch FILE with torn/mismatched content still commits its
        # epoch — the filename is the commit (the no-hardlink
        # fallback's brief torn window), content is only forensics
        with open(cluster.generation_path(d, 2), "w") as f:
            f.write("{torn")
        assert cluster.read_generation(d) == 2
        assert cluster.read_generation_record(d) == {"generation": 2}

    def test_stalled_writer_cannot_roll_the_epoch_backwards(
            self, tmp_path, monkeypatch):
        """The rollback race the exclusive-create publish closes: a
        writer that read generation 0, passed its expect pre-check,
        then stalled while the cluster moved to 2 must be REFUSED at
        publish time — not land epoch 1 over the committed 2."""
        from apex_tpu.cluster import membership as _membership
        d = str(tmp_path)
        cluster.bump_generation(d)                      # 0 -> 1
        cluster.bump_generation(d)                      # 1 -> 2
        # replay the stalled writer: its read happened BEFORE the two
        # bumps, so both its pre-check and its error-path re-read see
        # the stale 0 — only the publish-side exclusive create (the
        # target epoch-1 file already exists) can refuse it
        monkeypatch.setattr(_membership, "read_generation",
                            lambda _d: 0)
        with pytest.raises(cluster.StaleGenerationError):
            _membership.bump_generation(d, expect=0)
        monkeypatch.undo()
        assert cluster.read_generation(d) == 2


# --- leases -------------------------------------------------------------------

class TestLease:
    def test_acquire_renew_release_roundtrip(self, tmp_path):
        d = str(tmp_path)
        lw = cluster.LeaseWriter(d, rank=2, ttl_s=30.0)
        assert lw.acquire(0)
        t0 = time.time()
        leases = cluster.read_leases(d)
        assert set(leases) == {2}
        rec = leases[2]
        assert rec["generation"] == 0 and rec["rank"] == 2
        assert rec["pid"] == os.getpid()
        assert abs(rec["expires_at"] - (t0 + 30.0)) < 5.0
        assert isinstance(rec["mac"], str) and len(rec["mac"]) == 64
        assert lw.renew()
        assert cluster.read_leases(d)[2]["n_renewals"] == 1
        lw.release()
        assert cluster.read_leases(d) == {}

    def test_torn_lease_file_is_skipped(self, tmp_path):
        d = str(tmp_path)
        cluster.LeaseWriter(d, rank=0).acquire(0)
        with open(cluster.lease_path(d, 1), "w") as f:
            f.write('{"rank": 1, "gener')       # torn tail
        assert set(cluster.read_leases(d)) == {0}

    def test_expire_now_is_the_lease_expire_chaos_site(self, tmp_path):
        d = str(tmp_path)
        m = cluster.ClusterMembership(d, rank=0, ttl_s=60.0)
        m.join()
        assert m.alive_ranks() == [0]
        assert m.expired_ranks() == []
        assert m.lease.expire_now()
        assert m.alive_ranks() == []
        assert m.expired_ranks() == [0]

    def test_alive_ranks_excludes_other_generations(self, tmp_path):
        d = str(tmp_path)
        m0 = cluster.ClusterMembership(d, rank=0)
        m0.join()
        stale = cluster.LeaseWriter(d, rank=1, ttl_s=60.0)
        stale.acquire(0)
        assert m0.alive_ranks() == [0, 1]
        m0.bump("shrink")           # commits generation 1, re-leases
        # rank 1's unexpired lease still claims generation 0: not alive
        assert m0.alive_ranks() == [0]

    def test_gc_stale_leases(self, tmp_path):
        d = str(tmp_path)
        old = cluster.LeaseWriter(d, rank=1)
        old.acquire(0)
        cluster.bump_generation(d)
        cur = cluster.LeaseWriter(d, rank=0)
        cur.acquire(1)
        removed = cluster.gc_stale_leases(d, 1)
        assert removed == [cluster.lease_path(d, 1)]
        assert set(cluster.read_leases(d)) == {0}

    def test_foreign_lease_is_not_a_member_and_gc_eligible(self,
                                                           tmp_path):
        """A stray/foreign lease file (valid JSON, no valid MAC) must
        not read as a phantom member — it would stall every recovery
        barrier for the full timeout waiting on its intent — and gc
        may remove it even when its claimed generation is current."""
        d = str(tmp_path)
        m = cluster.ClusterMembership(d, rank=0, ttl_s=60.0)
        m.join()
        with open(cluster.lease_path(d, 5), "w") as f:
            json.dump({"rank": 5, "generation": 0,
                       "expires_at": time.time() + 1e6,
                       "mac": "f" * 64}, f)
        # raw read still sees it; the verified membership view doesn't
        assert 5 in cluster.read_leases(d)
        assert 5 not in m.leases()
        assert m.alive_ranks() == [0]
        removed = cluster.gc_stale_leases(d, 0,
                                          token=m.lease.token)
        assert removed == [cluster.lease_path(d, 5)]
        assert 0 in cluster.read_leases(d)


# --- the membership fence -----------------------------------------------------

class TestMembershipFence:
    def test_join_check_pass_at_current_generation(self, tmp_path):
        events, sink = _collect()
        m = cluster.ClusterMembership(str(tmp_path), rank=0,
                                      event_sink=sink)
        assert m.join() == 0
        assert m.check("commit") == 0
        assert [e["kind"] for e in events] == ["cluster_lease"]
        assert events[0]["action"] == "acquire"

    def test_zombie_check_refuses_and_emits_fence_event(self, tmp_path):
        d = str(tmp_path)
        zombie_events, zsink = _collect()
        zombie = cluster.ClusterMembership(d, rank=1, event_sink=zsink)
        zombie.join()
        other = cluster.ClusterMembership(d, rank=0)
        other.join()
        other.bump("recovery")      # the world moves on
        with pytest.raises(cluster.StaleGenerationError) as ei:
            zombie.check("commit", path="/ck/step_8", step=8)
        assert "zombie" in str(ei.value)
        fences = [e for e in zombie_events
                  if e["kind"] == "cluster_fence"]
        assert len(fences) == 1
        ev = fences[0]
        assert ev["action"] == "refused_commit"
        assert ev["generation"] == 0 and ev["current_generation"] == 1
        assert ev["path"] == "/ck/step_8" and ev["step"] == 8
        # write and delete refusals carry their own action names
        with pytest.raises(cluster.StaleGenerationError):
            zombie.check("write")
        with pytest.raises(cluster.StaleGenerationError):
            zombie.check("delete")
        acts = [e["action"] for e in zombie_events
                if e["kind"] == "cluster_fence"]
        assert acts == ["refused_commit", "refused_write",
                        "refused_delete"]

    def test_bump_emits_and_rejoin_adopts(self, tmp_path):
        d = str(tmp_path)
        events, sink = _collect()
        m = cluster.ClusterMembership(d, rank=0, event_sink=sink)
        m.join()
        assert m.bump("coordinated_rewind") == 1
        bumps = [e for e in events if e["kind"] == "cluster_generation"]
        assert bumps[0]["action"] == "bump"
        assert bumps[0]["generation"] == 1
        assert bumps[0]["prev_generation"] == 0
        follower = cluster.ClusterMembership(d, rank=1)
        follower.join()
        assert follower.generation == 1
        assert follower.check("commit") == 1

    def test_split_brain_claim_is_refused_everywhere(self, tmp_path):
        d = str(tmp_path)
        events, sink = _collect()
        m = cluster.ClusterMembership(d, rank=1, event_sink=sink)
        m.join()
        m.claim_generation(5)       # an epoch the cluster never agreed
        # the fence refuses ANY mismatch — a future claim is
        # split-brain, not seniority
        with pytest.raises(cluster.StaleGenerationError) as ei:
            m.check("commit")
        assert "split-brain" in str(ei.value)
        assert any(e["kind"] == "cluster_fence" and e["generation"] == 5
                   and e["current_generation"] == 0 for e in events)
        # and the CAS bump refuses to commit the claim
        with pytest.raises(cluster.StaleGenerationError):
            m.bump("split")         # expect=5, disk at 0
        assert cluster.read_generation(d) == 0

    def test_gc_stale_cleans_leases_heartbeats_intents(self, tmp_path):
        d = str(tmp_path)
        hb_dir = str(tmp_path / "hb")
        old = cluster.LeaseWriter(d, rank=7)
        old.acquire(0)
        hb = _straggler.HeartbeatWriter(hb_dir, rank=7, generation=0)
        hb.on_step(_FakeStepTrace(3, 10.0))
        # a resolved round's intent files are inert once the epoch
        # moved — but must not accumulate under the per-step
        # pending() listdir forever
        stale_member = cluster.ClusterMembership(d, rank=7)
        stale_member.join()
        stale_intent = cluster.RecoveryCoordinator(
            stale_member).propose(action="rewind", step=3, good_step=2)
        events, sink = _collect()
        m = cluster.ClusterMembership(d, rank=0, event_sink=sink)
        m.join()
        m.bump("restart")
        removed = m.gc_stale(heartbeat_dir=hb_dir)
        assert cluster.lease_path(d, 7) in removed
        assert _straggler.heartbeat_path(hb_dir, 7) in removed
        assert stale_intent in removed
        assert not os.path.exists(stale_intent)
        assert any(e["kind"] == "cluster_lease" and e["action"] == "gc"
                   for e in events)


# --- checkpoint-layer fencing -------------------------------------------------

class TestCkptFence:
    def _tree(self, v=1.0):
        return {"w": jnp.full((8,), v, jnp.float32)}

    def test_fenced_save_records_generation(self, tmp_path):
        d, root = str(tmp_path / "c"), str(tmp_path / "ck")
        m = cluster.ClusterMembership(d, rank=0)
        m.join()
        mgr = ckpt.CheckpointManager(root, fence=m, rank=0,
                                     process_count=1)
        mgr.save(1, self._tree(), block=True)
        mgr.wait()
        manifest = ckpt.read_manifest(ckpt.latest_checkpoint(root))
        assert manifest["generation"] == 0

    def test_zombie_save_is_refused_before_any_byte_lands(self,
                                                          tmp_path):
        d, root = str(tmp_path / "c"), str(tmp_path / "ck")
        events, sink = _collect()
        zombie = cluster.ClusterMembership(d, rank=0, event_sink=sink)
        zombie.join()
        mgr = ckpt.CheckpointManager(root, fence=zombie, rank=0,
                                     process_count=1)
        mgr.save(1, self._tree(), block=True)
        mgr.wait()
        other = cluster.ClusterMembership(d, rank=1)
        other.join()
        other.bump("relaunch")
        mgr.save(2, self._tree(2.0), block=True)
        with pytest.raises(cluster.StaleGenerationError):
            mgr.wait()
        # nothing of step 2 landed: no dir, latest still step 1
        assert not os.path.exists(ckpt.step_dir(root, 2))
        assert ckpt.latest_checkpoint(root) == ckpt.step_dir(root, 1)
        assert any(e["kind"] == "cluster_fence"
                   and e["action"] == "refused_write" for e in events)

    def test_zombie_gc_is_refused(self, tmp_path):
        d, root = str(tmp_path / "c"), str(tmp_path / "ck")
        fresh = cluster.ClusterMembership(d, rank=0)
        fresh.join()
        mgr = ckpt.CheckpointManager(root, fence=fresh, rank=0,
                                     process_count=1, keep=0)
        for s in (1, 2, 3):
            mgr.save(s, self._tree(float(s)), block=True)
        mgr.wait()
        zombie = cluster.ClusterMembership(d, rank=1)
        zombie.join()
        fresh.bump("relaunch")
        with pytest.raises(cluster.StaleGenerationError):
            ckpt.gc_checkpoints(root, keep=1, fence=zombie)
        assert len(ckpt.committed_steps(root)) == 3
        # the CURRENT generation's holder may gc
        removed = ckpt.gc_checkpoints(root, keep=1, fence=fresh)
        assert len(removed) == 2
        assert ckpt.committed_steps(root) == [3]

    def test_commit_manifest_explicit_generation(self, tmp_path):
        d = str(tmp_path / "step_00000001")
        _format.write_process_file(d, 0, [("['w']",
                                           np.zeros(4, np.float32))])
        _format.commit_manifest(d, step=1, process_count=1,
                                generation=7)
        assert _format.read_manifest(d)["generation"] == 7


# --- the in-use marker vs concurrent gc ---------------------------------------

class TestInUseMarker:
    def _committed(self, root, steps):
        for s in steps:
            d = ckpt.step_dir(root, s)
            _format.write_process_file(
                d, 0, [("['w']", np.full(4, float(s), np.float32))])
            _format.commit_manifest(d, step=s, process_count=1)

    def test_marker_pins_directory_against_gc(self, tmp_path):
        root = str(tmp_path)
        self._committed(root, [1, 2, 3])
        oldest = ckpt.step_dir(root, 1)
        with ckpt.checkpoint_in_use(oldest, rank=0):
            assert ckpt.checkpoint_is_in_use(oldest)
            removed = ckpt.gc_checkpoints(root, keep=1)
            assert oldest not in removed
            assert os.path.isdir(oldest)
            # the unpinned middle one went
            assert ckpt.step_dir(root, 2) in removed
        assert not ckpt.checkpoint_is_in_use(oldest)
        removed = ckpt.gc_checkpoints(root, keep=1)
        assert oldest in removed

    def test_marker_ttl_expires(self, tmp_path):
        root = str(tmp_path)
        self._committed(root, [1, 2])
        d = ckpt.step_dir(root, 1)
        marker = os.path.join(d, f"{_format.INUSE_PREFIX}rank00000."
                              f"{os.getpid()}.json")
        with open(marker, "w") as f:
            json.dump({"rank": 0, "pid": 1,
                       "wall_time": time.time() - 1e4}, f)
        # a reader that died long ago cannot pin the dir forever
        assert not ckpt.checkpoint_is_in_use(d, ttl_s=300.0)
        assert ckpt.checkpoint_is_in_use(d, ttl_s=1e6)

    def test_corrupt_marker_counts_as_live(self, tmp_path):
        root = str(tmp_path)
        self._committed(root, [1])
        d = ckpt.step_dir(root, 1)
        with open(os.path.join(d, f"{_format.INUSE_PREFIX}x.json"),
                  "w") as f:
            f.write("{torn")
        assert ckpt.checkpoint_is_in_use(d)

    def test_restore_pins_its_directory(self, tmp_path,
                                        monkeypatch):
        root = str(tmp_path)
        mgr = ckpt.CheckpointManager(root, rank=0, process_count=1)
        mgr.save(1, {"w": jnp.ones(4)}, block=True)
        mgr.wait()
        d = ckpt.latest_checkpoint(root)
        seen = {}
        orig = _format.assemble_arrays

        def spying(ckpt_dir, *a, **kw):
            seen["in_use"] = ckpt.checkpoint_is_in_use(ckpt_dir)
            return orig(ckpt_dir, *a, **kw)

        monkeypatch.setattr(_format, "assemble_arrays", spying)
        mgr.restore({"w": jnp.zeros(4)})
        assert seen["in_use"], \
            "restore gathered without the in-use marker"
        assert not ckpt.checkpoint_is_in_use(d)

    def test_two_process_gc_vs_restore_race(self, tmp_path):
        """A reader in ANOTHER process pins the oldest checkpoint; a
        concurrent gc pass must skip it this round and collect it once
        the reader exits — the mid-read delete race, made
        deterministic."""
        root = str(tmp_path / "ck")
        self._committed(root, [1, 2, 3])
        oldest = ckpt.step_dir(root, 1)
        ready = str(tmp_path / "ready")
        go = str(tmp_path / "go")
        child = textwrap.dedent(f"""
            import os, sys, time
            sys.path.insert(0, {_REPO_ROOT!r})
            from apex_tpu.ckpt import format as f
            with f.checkpoint_in_use({oldest!r}, rank=1):
                open({ready!r}, "w").close()
                t0 = time.monotonic()
                while (not os.path.exists({go!r})
                       and time.monotonic() - t0 < 60):
                    time.sleep(0.02)
        """)
        p = subprocess.Popen([sys.executable, "-c", child],
                             cwd=_REPO_ROOT)
        try:
            t0 = time.monotonic()
            while not os.path.exists(ready):
                assert time.monotonic() - t0 < 60, "reader never pinned"
                time.sleep(0.02)
            removed = ckpt.gc_checkpoints(root, keep=1)
            assert oldest not in removed, \
                "gc deleted a checkpoint a live reader holds"
            assert os.path.isdir(oldest)
            assert _format.read_manifest(oldest)["step"] == 1
        finally:
            open(go, "w").close()
            p.wait(timeout=60)
        removed = ckpt.gc_checkpoints(root, keep=1)
        assert oldest in removed        # the reader left; next round


# --- the recovery coordinator -------------------------------------------------

def _member(d, rank, sink=None):
    m = cluster.ClusterMembership(d, rank=rank, event_sink=sink)
    m.join()
    return m


class TestCoordinator:
    def test_propose_pending_verify_roundtrip(self, tmp_path):
        d = str(tmp_path)
        m0, m1 = _member(d, 0), _member(d, 1)
        c0 = cluster.RecoveryCoordinator(m0, barrier_timeout_s=1.0)
        c1 = cluster.RecoveryCoordinator(m1, barrier_timeout_s=1.0)
        assert not c0.peer_requested()
        c1.propose(action="rewind", step=8, good_step=6)
        assert c0.peer_requested()
        pend = c0.pending()
        assert set(pend) == {1}
        assert pend[1]["good_step"] == 6 and pend[1]["action"] == \
            "rewind"
        assert c0.last_refused == ()

    def test_tampered_intent_is_refused(self, tmp_path):
        d = str(tmp_path)
        events, sink = _collect()
        m0 = _member(d, 0, sink)
        m1 = _member(d, 1)
        c0 = cluster.RecoveryCoordinator(m0, barrier_timeout_s=1.0)
        c1 = cluster.RecoveryCoordinator(m1, barrier_timeout_s=1.0)
        path = c1.propose(action="rewind", step=8, good_step=6)
        rec = json.load(open(path))
        rec["good_step"] = 0            # tamper without re-MACing
        with open(path, "w") as f:
            json.dump(rec, f)
        assert c0.pending() == {}
        assert c0.last_refused == (1,)
        refusals = [e for e in events if e["kind"] == "cluster_fence"]
        assert refusals and refusals[0]["action"] == "refused_intent"
        assert "bad signature" in refusals[0]["reason"]

    def test_split_brain_intent_is_refused(self, tmp_path):
        d = str(tmp_path)
        events, sink = _collect()
        m0 = _member(d, 0, sink)
        m1 = _member(d, 1)
        m1.claim_generation(3)      # the split_brain chaos site
        c0 = cluster.RecoveryCoordinator(m0, barrier_timeout_s=1.0)
        c1 = cluster.RecoveryCoordinator(m1, barrier_timeout_s=1.0)
        c1.propose(action="rewind", step=8, good_step=6)
        # the claimed epoch's intent lands under its OWN prefix — the
        # verifier at the committed generation never even counts it,
        # and a same-prefix forgery is refused by generation check
        assert c0.pending() == {}
        assert not c0.peer_requested()
        # forge the filename down to the committed generation: the
        # payload still claims generation 3 — refused, with evidence
        src = cluster.intent_path(d, 3, 1)
        dst = cluster.intent_path(d, 0, 1)
        os.replace(src, dst)
        assert c0.pending() == {}
        assert c0.last_refused == (1,)
        refusals = [e for e in events if e["kind"] == "cluster_fence"]
        assert refusals[-1]["action"] == "refused_intent"
        assert "claims generation 3" in refusals[-1]["reason"]

    def test_resolve_oldest_good_step_wins_single_bump(self, tmp_path):
        d = str(tmp_path)
        events, sink = _collect()
        m0, m1 = _member(d, 0, sink), _member(d, 1, sink)
        c0 = cluster.RecoveryCoordinator(m0, barrier_timeout_s=5.0)
        c1 = cluster.RecoveryCoordinator(m1, barrier_timeout_s=5.0)
        c0.propose(action="rewind", step=9, good_step=8)
        c1.propose(action="rewind", step=9, good_step=6)
        d0 = c0.resolve(expect_ranks=[0, 1])    # leader: bumps
        d1 = c1.resolve(expect_ranks=[0, 1])    # follower: observes
        for dec in (d0, d1):
            assert dec.action == "rewind"
            assert dec.target_step == 6         # oldest good wins
            assert dec.ranks == (0, 1) and dec.leader == 0
            assert dec.generation == 0 and dec.new_generation == 1
        assert cluster.read_generation(d) == 1
        bumps = [e for e in events
                 if e["kind"] == "cluster_generation"
                 and e["action"] == "bump"]
        assert len(bumps) == 1, "generation must bump exactly once"
        assert m0.generation == 1 and m1.generation == 1

    def test_escalate_dominates_and_none_good_forces_it(self,
                                                        tmp_path):
        d = str(tmp_path)
        m0, m1 = _member(d, 0), _member(d, 1)
        c0 = cluster.RecoveryCoordinator(m0, barrier_timeout_s=5.0)
        c1 = cluster.RecoveryCoordinator(m1, barrier_timeout_s=5.0)
        c0.propose(action="rewind", step=9, good_step=8)
        c1.propose(action="escalate", step=9, good_step=6)
        dec = c0.resolve(expect_ranks=[0, 1], bump=False)
        assert dec.action == "escalate" and dec.target_step is None

        d2 = str(tmp_path / "none")
        m0b, m1b = _member(d2, 0), _member(d2, 1)
        c0b = cluster.RecoveryCoordinator(m0b, barrier_timeout_s=5.0)
        c1b = cluster.RecoveryCoordinator(m1b, barrier_timeout_s=5.0)
        c0b.propose(action="rewind", step=9, good_step=8)
        c1b.propose(action="rewind", step=9, good_step=None)
        dec = c0b.resolve(expect_ranks=[0, 1], bump=False)
        assert dec.action == "escalate", \
            "a rank with NO restorable checkpoint forces escalation"

    def test_barrier_timeout_proceeds_with_present_intents(self,
                                                           tmp_path):
        d = str(tmp_path)
        events, sink = _collect()
        m0 = _member(d, 0, sink)
        c0 = cluster.RecoveryCoordinator(m0, barrier_timeout_s=0.3)
        c0.propose(action="rewind", step=9, good_step=4)
        t0 = time.monotonic()
        dec = c0.resolve(expect_ranks=[0, 1], bump=False)
        assert time.monotonic() - t0 < 10.0
        assert dec.action == "rewind" and dec.target_step == 4
        assert dec.ranks == (0,)
        timeouts = [e for e in events
                    if e.get("action") == "barrier_timeout"]
        assert timeouts and timeouts[0]["missing"] == [1]

    def test_zero_intents_raises_coordination_error(self, tmp_path):
        m0 = _member(str(tmp_path), 0)
        c0 = cluster.RecoveryCoordinator(m0, barrier_timeout_s=0.2)
        with pytest.raises(cluster.CoordinationError):
            c0.resolve(expect_ranks=[1])

    def test_invalid_action_refused_at_the_door(self, tmp_path):
        m0 = _member(str(tmp_path), 0)
        c0 = cluster.RecoveryCoordinator(m0)
        with pytest.raises(ValueError):
            c0.propose(action="reboot", step=1, good_step=0)


# --- coordinated rewind through GuardPolicy (in-process, 2 logical ranks) -----

class TestCoordinatedRewindInProcess:
    """The deterministic-resolution property, driven through real
    GuardPolicy/CheckpointManager instances for two logical ranks over
    one shared cluster directory — the multi-PROCESS acceptance twin is
    TestCoordinatedRewindAcceptance."""

    def test_both_ranks_land_on_the_common_target(self, tmp_path):
        d = str(tmp_path / "cluster")
        events, sink = _collect()
        members = [_member(d, r, sink) for r in (0, 1)]
        coords = [cluster.RecoveryCoordinator(m, barrier_timeout_s=10.0)
                  for m in members]
        mgrs, policies, likes = [], [], []
        for r in (0, 1):
            mgr = ckpt.CheckpointManager(
                str(tmp_path / f"ck_r{r}"), fence=members[r], rank=0,
                process_count=1, keep=0)
            # rank-local histories: rank 1's newest checkpoint captured
            # NaN params (the rank-asymmetric corruption), rank 0's is
            # healthy — so their newest GOOD steps differ (8 vs 6)
            for s in (4, 6, 8):
                bad = (r == 1 and s == 8)
                w = np.full((4,), np.nan if bad else float(s),
                            np.float32)
                mgr.save(s, {"w": jnp.asarray(w)},
                         extra={"cursor": {"index": s}}, block=True)
                mgr.wait()
            mgrs.append(mgr)
            policies.append(guard.GuardPolicy(manager=mgr))
            likes.append({"w": jnp.zeros((4,), jnp.float32)})
        assert policies[0].probe_good_step(likes[0]) == 8
        assert policies[1].probe_good_step(likes[1]) == 6

        src = _FakeCursorSource()
        # rank 1 detected the corruption; rank 0 is healthy but joins
        coords[1].propose(action="rewind", step=9,
                          good_step=policies[1].probe_good_step(
                              likes[1]))
        assert coords[0].peer_requested()
        dec0, res0 = coords[0].run_round(policies[0], 9, likes[0], src,
                                         expect_ranks=[0, 1])
        dec1, res1 = coords[1].run_round(policies[1], 9, likes[1], src,
                                         expect_ranks=[0, 1])
        for dec in (dec0, dec1):
            assert dec.action == "rewind" and dec.target_step == 6
            assert dec.new_generation == 1
        # BOTH ranks restored step 6 — rank 0 honored the cluster
        # target over its own newer good checkpoint
        for r, res in ((0, res0), (1, res1)):
            restored, manifest = res
            assert manifest["step"] == 6
            assert np.allclose(np.asarray(restored["w"]), 6.0)
        assert cluster.read_generation(d) == 1
        bumps = [e for e in events
                 if e["kind"] == "cluster_generation"
                 and e["action"] == "bump"]
        assert len(bumps) == 1
        # the whole exchange validates as a cluster event stream
        lines = [json.dumps(e) for e in events]
        assert not check_cluster_lines(lines)

    def test_unloadable_agreed_target_escalates_not_diverges(
            self, tmp_path):
        """A rank that cannot restore the AGREED target must escalate
        — rewind's fallback chain restoring an older step would put
        this rank on a different history than its peers, the exact
        split-brain the round exists to prevent."""
        d = str(tmp_path / "cluster")
        _, sink = _collect()
        members = [_member(d, r, sink) for r in (0, 1)]
        coords = [cluster.RecoveryCoordinator(m, barrier_timeout_s=10.0)
                  for m in members]
        mgrs, policies, likes = [], [], []
        for r in (0, 1):
            mgr = ckpt.CheckpointManager(
                str(tmp_path / f"ck_r{r}"), fence=members[r], rank=0,
                process_count=1, keep=0)
            # rank 1's newest (8) is NaN -> its good step is 6; rank 0
            # is all-healthy (good step 8)
            for s in (4, 6, 8):
                bad = (r == 1 and s == 8)
                w = np.full((4,), np.nan if bad else float(s),
                            np.float32)
                mgr.save(s, {"w": jnp.asarray(w)},
                         extra={"cursor": {"index": s}}, block=True)
                mgr.wait()
            mgrs.append(mgr)
            policies.append(guard.GuardPolicy(manager=mgr))
            likes.append({"w": jnp.zeros((4,), jnp.float32)})
        # truncate rank 0's copy of the agreed target (step 6) AFTER
        # it voted: the hash check rejects it at restore time and the
        # fallback chain would silently land on step 4
        tgt = _format.step_dir(str(tmp_path / "ck_r0"), 6)
        proc = os.path.join(tgt, "proc00000.npz")
        with open(proc, "r+b") as f:
            f.truncate(16)
        coords[1].propose(action="rewind", step=9,
                          good_step=policies[1].probe_good_step(
                              likes[1]))
        src = _FakeCursorSource()
        with pytest.raises(guard.GuardEscalation) as exc:
            coords[0].run_round(policies[0], 9, likes[0], src,
                                expect_ranks=[0, 1])
        assert "coordinated rewind diverged" in str(exc.value)
        assert "agreed on step 6" in str(exc.value)


class _FakeCursorSource:
    """Minimal GuardPolicy.rewind source: cursor only, no decode."""

    def __init__(self):
        self._index = 9

    def cursor_index(self):
        return self._index

    def load_state(self, state):
        self._index = int(state.get("index", 0)) if isinstance(
            state, dict) else 0

    def skip_batches(self, n):
        self._index += int(n)


# --- collective deadline ------------------------------------------------------

class _FakeTracer:
    def __init__(self):
        self.probe = None

    def in_flight_collective_age(self):
        return self.probe


class _TripSpy:
    def __init__(self):
        self.reasons = []

    def trip(self, reason):
        self.reasons.append(reason)


class TestCollectiveDeadline:
    def test_slow_collective_does_not_fire(self):
        tr = _FakeTracer()
        cd = cluster.CollectiveDeadline(tr, deadline_s=10.0)
        assert cd.poll_once() is None          # nothing open
        tr.probe = ("ddp/sync_gradients", 2.0)
        assert cd.poll_once() is None          # open but young
        assert cd.fired == 0

    def test_hung_collective_fires_once_per_instance(self):
        tr = _FakeTracer()
        spy = _TripSpy()
        events, sink = _collect()
        cd = cluster.CollectiveDeadline(tr, deadline_s=5.0,
                                        escalation=spy,
                                        event_sink=sink,
                                        generation=lambda: 2)
        # the third probe element is the span's STABLE start stamp —
        # the instance identity the fire-once logic keys on (a
        # re-derived now−age would drift between polls)
        tr.probe = ("ddp/sync_gradients", 7.5, 100.0)
        ev = cd.poll_once()
        assert ev is not None
        assert ev["action"] == "collective_hang"
        assert ev["collective"] == "ddp/sync_gradients"
        assert ev["generation"] == 2
        assert spy.reasons == ["collective:ddp/sync_gradients"]
        # the SAME span instance (age grows, start fixed) never refires
        tr.probe = ("ddp/sync_gradients", 8.5, 100.0)
        assert cd.poll_once() is None
        assert cd.fired == 1
        # a NEW instance (fresh start: the old one closed) re-arms
        tr.probe = None
        assert cd.poll_once() is None
        tr.probe = ("ddp/sync_gradients", 9.0, 200.0)
        assert cd.poll_once() is not None
        assert cd.fired == 2
        assert not check_cluster_lines([json.dumps(e) for e in events])

    def test_tracer_reports_open_collective_age(self):
        tracer = trace.Tracer()
        with tracer:
            assert tracer.in_flight_collective_age() is None
            with trace.step(0):
                with trace.span("fwd"):
                    pass            # a plain span is not a collective
                assert tracer.in_flight_collective_age() is None
                with trace.span("ddp/sync_gradients",
                                kind="collective"):
                    probe = tracer.in_flight_collective_age()
                    assert probe is not None
                    name, age, start = probe
                    assert name == "ddp/sync_gradients"
                    assert 0.0 <= age < 60.0
                    # the start stamp is stable across polls — the
                    # fire-once instance identity
                    assert tracer.in_flight_collective_age()[2] == \
                        start
                assert tracer.in_flight_collective_age() is None

    def test_daemon_lifecycle(self):
        tr = _FakeTracer()
        cd = cluster.CollectiveDeadline(tr, deadline_s=0.05,
                                        poll_s=0.02)
        tr.probe = ("zero/grad_scatter", 1.0)
        with cd:
            t0 = time.monotonic()
            while cd.fired == 0 and time.monotonic() - t0 < 10.0:
                time.sleep(0.02)
        assert cd.fired >= 1

    def test_enable_crash_dumps_returns_deadline_tier(self, tmp_path):
        from apex_tpu import parallel
        out = parallel.enable_crash_dumps(
            str(tmp_path / "crash.jsonl"),
            collective_deadline_s=60.0)
        assert len(out) == 4
        tracer, recorder, wd, deadline = out
        assert isinstance(deadline, cluster.CollectiveDeadline)
        deadline.stop()
        recorder.uninstall()


# --- generation-scoped heartbeats / straggler ---------------------------------

class _FakeStepTrace:
    def __init__(self, step, dur_ms, spans=None):
        self.step = step
        self.spans = []
        self._dur = dur_ms
        self._spans = spans or {}

    def span_ms(self):
        return dict(self._spans)

    @property
    def dur_ms(self):
        return self._dur


def _beat(directory, rank, steps, dur_ms, generation=None):
    w = _straggler.HeartbeatWriter(directory, rank=rank,
                                   generation=generation)
    for s in steps:
        w.on_step(_FakeStepTrace(s, dur_ms))
    return w


class TestHeartbeatGeneration:
    def test_generation_scoped_read(self, tmp_path):
        d = str(tmp_path)
        _beat(d, 0, [1, 2], 10.0, generation=1)
        _beat(d, 1, [1, 2], 10.0)               # untagged = gen 0
        allb = _straggler.read_heartbeats(d)
        assert set(allb) == {0, 1}
        g1 = _straggler.read_heartbeats(d, generation=1)
        assert set(g1) == {0}
        g0 = _straggler.read_heartbeats(d, generation=0)
        assert set(g0) == {1}
        assert g1[0][1]["generation"] == 1

    def test_set_generation_retags_across_a_bump(self, tmp_path):
        d = str(tmp_path)
        w = _straggler.HeartbeatWriter(d, rank=0, generation=0)
        w.on_step(_FakeStepTrace(1, 10.0))
        w.set_generation(1)
        w.on_step(_FakeStepTrace(2, 10.0))
        g1 = _straggler.read_heartbeats(d, generation=1)
        assert set(g1[0]) == {2}

    def test_gc_stale_heartbeats_keeps_survivors(self, tmp_path):
        d = str(tmp_path)
        _beat(d, 0, [1, 2], 10.0, generation=0)     # dead old rank
        surv = _straggler.HeartbeatWriter(d, rank=1, generation=0)
        surv.on_step(_FakeStepTrace(1, 10.0))
        surv.set_generation(1)
        surv.on_step(_FakeStepTrace(2, 10.0))       # survivor crossed
        removed = _straggler.gc_stale_heartbeats(d, 1)
        assert removed == [_straggler.heartbeat_path(d, 0)]
        assert set(_straggler.read_heartbeats(d)) == {1}

    def test_detector_ignores_stale_generation_laggard(self, tmp_path):
        d = str(tmp_path)
        # generation-0 history says rank 2 lags badly; the cluster is
        # at generation 1 where every rank is healthy
        for r in (0, 1):
            _beat(d, r, range(8), 10.0, generation=0)
        _beat(d, 2, range(8), 500.0, generation=0)
        for r in (0, 1, 2):
            _beat(d, r, range(8, 16), 10.0, generation=1)
        det = _straggler.StragglerDetector(d, window=8, hysteresis=2,
                                           generation=1)
        assert det.check() == []
        stale_view = _straggler.StragglerDetector(d, window=8,
                                                  hysteresis=2,
                                                  generation=0)
        flagged = stale_view.check()
        assert flagged and flagged[0].rank == 2

    def test_dead_rank_is_not_a_silent_rank_after_gc(self, tmp_path):
        """The satellite bug: a dead rank's last heartbeat read as a
        silent rank forever. After relaunch hygiene (gc + generation
        scoping) the detector simply no longer sees the dead rank."""
        d = str(tmp_path)
        _beat(d, 0, range(8), 10.0, generation=0)   # died in gen 0
        for r in (1, 2):
            w = _beat(d, r, range(8), 10.0, generation=0)
            w.set_generation(1)
            for s in range(8, 16):
                w.on_step(_FakeStepTrace(s, 10.0))
        _straggler.gc_stale_heartbeats(d, 1)
        beats = _straggler.read_heartbeats(d, generation=1)
        assert set(beats) == {1, 2}
        det = _straggler.StragglerDetector(d, window=8, generation=1)
        assert det.check() == []


# --- elastic_run v2 relaunch hygiene ------------------------------------------

class TestElasticRelaunchHygiene:
    def test_relaunch_bumps_and_cleans(self, tmp_path):
        d, hb = str(tmp_path / "c"), str(tmp_path / "hb")
        stale = cluster.LeaseWriter(d, rank=1)
        stale.acquire(0)
        _beat(hb, 1, [1, 2], 10.0, generation=0)
        events, sink = _collect()
        gen = cluster.relaunch(d, reason="elastic_restart:1",
                               heartbeat_dir=hb, event_sink=sink)
        assert gen == 1
        assert cluster.read_generation(d) == 1
        assert cluster.read_leases(d) == {}, \
            "relaunch must leave a clean lease table (incl. its own)"
        assert _straggler.read_heartbeats(hb) == {}
        assert not check_cluster_lines([json.dumps(e) for e in events])

    def test_elastic_run_fences_each_restart(self, tmp_path):
        from apex_tpu.parallel.launch import elastic_run
        d, hb = str(tmp_path / "c"), str(tmp_path / "hb")
        seen, events = [], []

        def train(world, attempt):
            seen.append((world, attempt, cluster.read_generation(d)))
            if attempt == 0:
                # the failing attempt leaves the stale debris a real
                # dead rank leaves: an EXPIRED rank-0 lease and a
                # heartbeat file (rank 0 because the controller's own
                # default rank collides with it — the report must
                # still see the dead member, not overwrite its lease)
                dead = cluster.LeaseWriter(d, rank=0)
                dead.acquire(0)
                dead.expire_now()
                _beat(hb, 0, [1], 10.0, generation=0)
                raise ckpt.PreemptionError("rank died")
            assert cluster.read_leases(d) == {}
            assert _straggler.read_heartbeats(hb) == {}

        elastic_run(train, world_sizes=[8, 4], cluster_dir=d,
                    heartbeat_dir=hb, event_sink=events.append)
        assert seen == [(8, 0, 0), (4, 1, 1)], \
            "the restart must run under a freshly bumped generation"
        # the dead rank was REPORTED (lease observed expired), not
        # silently overwritten by the controller's own lease
        expires = [e for e in events
                   if e["kind"] == "cluster_lease"
                   and e["action"] == "expire"]
        assert expires and expires[0]["expired_rank"] == 0


# --- event schema + logger channel --------------------------------------------

class TestClusterSchema:
    def _valid(self):
        return [
            {"kind": "cluster_lease", "action": "acquire",
             "generation": 0, "rank": 0, "ttl_s": 30.0,
             "wall_time": 1.0, "path": "/c/lease.rank00000.json"},
            {"kind": "cluster_generation", "action": "bump",
             "generation": 1, "prev_generation": 0, "rank": 0,
             "reason": "coordinated_rewind", "wall_time": 2.0},
            {"kind": "cluster_fence", "action": "refused_commit",
             "generation": 0, "current_generation": 1, "rank": 1,
             "what": "commit", "path": None, "step": None,
             "reason": None, "wall_time": 3.0},
            {"kind": "cluster_coord", "action": "resolve",
             "generation": 1, "new_generation": 2, "rank": 0,
             "decided": "rewind", "target_step": 6, "ranks": [0, 1],
             "leader": 0, "n_refused": 0, "timed_out": False,
             "wall_time": 4.0},
            {"kind": "cluster_coord", "action": "collective_hang",
             "generation": 2, "rank": 1,
             "collective": "ddp/sync_gradients", "age_s": 130.0,
             "deadline_s": 120.0, "wall_time": 5.0},
        ]

    def test_valid_stream_passes(self):
        lines = [json.dumps(e) for e in self._valid()]
        assert not check_cluster_lines(lines)

    def test_negative_twins(self):
        ok = self._valid()

        def bad(i, **kw):
            rec = dict(ok[i])
            rec.update(kw)
            return [json.dumps(rec)]

        # unknown kind / unknown action
        assert check_cluster_lines(
            ['{"kind": "cluster_party", "action": "acquire", '
             '"generation": 0}'])
        assert check_cluster_lines(bad(0, action="evict"))
        # a fence action on a lease record
        assert check_cluster_lines(bad(0, action="refused_commit"))
        # missing required keys
        assert check_cluster_lines(
            ['{"kind": "cluster_fence", "action": "refused_commit", '
             '"generation": 0}'])        # no current_generation
        # negative / boolean generation
        assert check_cluster_lines(bad(1, generation=-1))
        assert check_cluster_lines(bad(1, generation=True))
        # a bump that goes backwards
        assert check_cluster_lines(bad(1, generation=0,
                                       prev_generation=3))
        # non-monotone bumps ACROSS the stream
        seq = [json.dumps(dict(ok[1], generation=3,
                               prev_generation=2)),
               json.dumps(dict(ok[1], generation=1,
                               prev_generation=0))]
        assert check_cluster_lines(seq)
        # null in a non-nullable field
        assert check_cluster_lines(bad(2, generation=None))
        # target_step IS nullable on an escalate resolve
        assert not check_cluster_lines(bad(3, decided="escalate",
                                           target_step=None))
        # ranks must be a list of non-negative ints
        assert check_cluster_lines(bad(3, ranks=[0, -1]))
        assert check_cluster_lines(bad(3, ranks="0,1"))
        # negative deadline
        assert check_cluster_lines(bad(4, deadline_s=-1.0))

    def test_logger_channel_is_unbuffered_and_nulls_nonfinite(
            self, tmp_path):
        path = str(tmp_path / "cluster.jsonl")
        logger = monitor.MetricsLogger(
            sinks=[], cluster_sink=monitor.JSONLSink(path),
            flush_every=1000)       # buffering would hide a crash loss
        logger.record_cluster({"kind": "cluster_coord",
                               "action": "collective_hang",
                               "generation": 0, "rank": 1,
                               "collective": "ddp/sync_gradients",
                               "age_s": 130.0,
                               "deadline_s": float("nan"),
                               "wall_time": time.time()})
        # readable BEFORE close: the refusal survives the zombie exit
        lines = open(path).read().splitlines()
        assert len(lines) == 1
        rec = json.loads(lines[0])
        assert rec["deadline_s"] is None, "non-finite must be nulled"
        logger.close()
        assert not check_cluster_lines(lines)

    def test_membership_events_validate_end_to_end(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        logger = monitor.MetricsLogger(
            sinks=[], cluster_sink=monitor.JSONLSink(path))
        d = str(tmp_path / "c")
        m = cluster.ClusterMembership(d, rank=0,
                                      event_sink=logger.record_cluster)
        m.join()
        m.heartbeat()
        m.bump("test")
        m.lease.expire_now()
        m.expired_ranks()
        m.leave()
        logger.close()
        lines = open(path).read().splitlines()
        assert not check_cluster_lines(lines)
        kinds = [json.loads(l)["kind"] for l in lines]
        assert kinds.count("cluster_generation") == 1
        assert kinds.count("cluster_lease") >= 3   # acquire/expire/rel


# --- chaos sites --------------------------------------------------------------

class TestClusterChaosSites:
    def test_sites_registered_and_validated(self):
        assert guard.chaos.SITES["cluster"] == (
            "lease_expire", "zombie_resume", "split_brain")
        plan = guard.FaultPlan(seed=1).add(3, "cluster",
                                           "lease_expire")
        rt = guard.FaultPlan.from_json(plan.to_json())
        assert rt.at(3, 0, "cluster").kind == "lease_expire"
        with pytest.raises(ValueError):
            guard.FaultPlan(seed=1).add(3, "cluster", "explode")

    def test_lease_expire_site(self, tmp_path):
        d = str(tmp_path)
        m = cluster.ClusterMembership(d, rank=0, ttl_s=60.0)
        m.join()
        plan = guard.FaultPlan(seed=1).add(2, "cluster",
                                           "lease_expire")
        h = guard.ChaosHarness(plan)
        state = {"w": np.ones(2)}
        h.post_step(1, state, membership=m)
        assert m.expired_ranks() == []
        h.post_step(2, state, membership=m)
        assert m.expired_ranks() == [0]
        assert h.injected == [(2, "cluster", "lease_expire")]

    def test_split_brain_site(self, tmp_path):
        d = str(tmp_path)
        m = cluster.ClusterMembership(d, rank=1, ttl_s=60.0)
        m.join()
        plan = guard.FaultPlan(seed=1).add(2, "cluster", "split_brain",
                                           rank=1)
        h = guard.ChaosHarness(plan, rank=1)
        h.post_step(2, {"w": np.ones(2)}, membership=m)
        assert m.generation == 1           # claimed, never committed
        assert cluster.read_generation(d) == 0
        with pytest.raises(cluster.StaleGenerationError):
            m.bump("post-split")           # the CAS refuses the claim

    def test_cluster_fault_requires_membership(self, tmp_path):
        plan = guard.FaultPlan(seed=1).add(2, "cluster",
                                           "lease_expire")
        h = guard.ChaosHarness(plan)
        with pytest.raises(ValueError):
            h.post_step(2, {"w": np.ones(2)})


# --- failed bench rows in a trajectory ------------------------------------------

class TestSentinelSkipsFailedRows:
    def test_sentinel_skips_the_failure_row_with_its_reason(self,
                                                            tmp_path):
        from apex_tpu.prof import sentinel
        p = str(tmp_path / "row06.json")
        with open(p, "w") as f:
            json.dump({"parsed": None, "rc": 13,
                       "failure_reason": "backend init failed: "
                                         "no TPU"}, f)
        rows = sentinel.load_rows([p])
        assert len(rows) == 1
        assert rows[0]["row"] is None
        assert "no TPU" in rows[0]["note"]


# --- acceptance: the SIGSTOP zombie is fenced ---------------------------------

_ZOMBIE_CHILD = textwrap.dedent("""
    import os, signal, sys, time
    import jax
    from apex_tpu import _compat
    jax.config.update("jax_platforms", "cpu")
    _compat.request_cpu_devices(4)

    root, cluster_dir, barrier, events = sys.argv[1:5]
    rank = int(sys.argv[5])

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from apex_tpu import ckpt, cluster, monitor, trace

    mesh = Mesh(np.array(jax.devices()), ("data",))

    def beat(r, i):
        open(os.path.join(barrier, f"beat_{r}_{i}"), "w").close()

    def wait_peer(r, i):
        p = os.path.join(barrier, f"beat_{r}_{i}")
        while not os.path.exists(p):   # the "collective": wedges when
            time.sleep(0.02)           # the peer pauses or dies

    logger = monitor.MetricsLogger(
        sinks=[], cluster_sink=monitor.JSONLSink(events))
    member = cluster.ClusterMembership(
        cluster_dir, rank=rank, ttl_s=2.0,
        event_sink=logger.record_cluster)
    assert member.join() == 0

    mgr = ckpt.CheckpointManager(root, fence=member, rank=rank,
                                 process_count=2, keep=0,
                                 barrier_timeout_s=60)
    policy = ckpt.EscalationPolicy(mgr)        # exit mode, code 75
    wd = None
    if rank == 0:
        wd = trace.HangWatchdog(deadline_s=4.0, poll_s=0.2,
                                on_stall=policy).start()

    np_rng = np.random.RandomState(0)
    w = jnp.asarray(np_rng.randn(16, 1), jnp.float32)
    xg = np_rng.randn(32, 16).astype("float32")
    yg = np_rng.randn(32, 1).astype("float32")

    def stepf(w, x, y):
        g = jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)
        g = jax.lax.pmean(g, "data")
        return w - 0.1 * g, jnp.mean((x @ w - y) ** 2)

    spmd = jax.jit(jax.shard_map(
        stepf, mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False))

    for i in range(1, 10):
        w, loss = spmd(w, xg, yg)
        float(np.asarray(loss))
        member.heartbeat()
        beat(rank, i)
        if rank == 1 and i == 4:
            print("RANK1 PAUSING", flush=True)
            os.kill(os.getpid(), signal.SIGSTOP)
            # ---- resumed: a zombie of generation 0. The world moved
            # on (escalation + relaunch bumped to generation 1); every
            # late mutation must be refused by the fence. ----
            print("RANK1 RESUMED", flush=True)
            refusals = 0
            try:
                mgr.save(99, {"w": w, "i": jnp.int32(99)}, block=True)
                mgr.wait()
                print("ZOMBIE COMMITTED", flush=True)
            except cluster.StaleGenerationError:
                refusals += 1
                print("ZOMBIE WRITE FENCED", flush=True)
            try:
                ckpt.gc_checkpoints(root, keep=1, fence=member)
                print("ZOMBIE DELETED", flush=True)
            except cluster.StaleGenerationError:
                refusals += 1
                print("ZOMBIE DELETE FENCED", flush=True)
            logger.close()
            sys.exit(88 if refusals == 2 else 1)
        wait_peer(1 - rank, i)
        mgr.snapshot(i, {"w": w, "i": jnp.int32(i)})
        if i in (1, 3):
            mgr.save(i, {"w": w, "i": jnp.int32(i)}, block=True)
        if wd is not None:
            wd.notify_step(i)
        print(f"STEP {i} rank {rank}", flush=True)
    print("FINISHED WITHOUT ESCALATION", flush=True)
""")

_NEWGEN_CHILD = textwrap.dedent("""
    import os, sys
    import jax
    from apex_tpu import _compat
    jax.config.update("jax_platforms", "cpu")
    _compat.request_cpu_devices(4)
    jax.config.update("jax_default_matmul_precision", "highest")

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from apex_tpu import ckpt, cluster

    root, cluster_dir = sys.argv[1:3]
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rep = NamedSharding(mesh, P())

    member = cluster.ClusterMembership(cluster_dir, rank=0)
    gen = member.join()
    assert gen == 1, f"relaunch should have bumped: {gen}"

    mgr = ckpt.CheckpointManager(root, fence=member, rank=0,
                                 process_count=1, keep=4)
    like = {"w": jax.device_put(jnp.zeros((16, 1), jnp.float32), rep),
            "i": jax.device_put(jnp.int32(0), rep)}
    restored, manifest = mgr.restore(like)
    print("RESTORED_STEP", manifest["step"], flush=True)
    w = restored["w"]

    np_rng = np.random.RandomState(0)
    xg = np_rng.randn(32, 16).astype("float32")
    yg = np_rng.randn(32, 1).astype("float32")

    def stepf(w, x, y):
        g = jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)
        g = jax.lax.pmean(g, "data")
        return w - 0.1 * g, jnp.mean((x @ w - y) ** 2)

    spmd = jax.jit(jax.shard_map(
        stepf, mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False))

    for k in range(3):
        w, loss = spmd(w, xg, yg)
        print("LOSS", float(np.asarray(loss)).hex(), flush=True)
        member.heartbeat()
        mgr.save(101 + k, {"w": w, "i": jnp.int32(101 + k)},
                 block=True)
    mgr.wait()
    latest = ckpt.latest_checkpoint(root)
    print("LATEST", os.path.basename(latest),
          ckpt.read_manifest(latest)["generation"], flush=True)
""")


class TestZombieAcceptance:
    @pytest.mark.slow          # 4 subprocess jax bring-ups (~1 min);
    #                            the in-process twin runs in smoke via
    #                            scripts/cluster_audit.py --cpu8
    def test_sigstop_zombie_commit_is_fenced(self, tmp_path):
        """2 procs × 4 CPU devices. Rank 1 SIGSTOPs itself mid-run;
        rank 0 wedges on the cross-rank sync, its watchdog escalates
        (checkpoint + exit 75) and the controller relaunches under a
        bumped generation. Rank 1 is then resumed — a live zombie of
        generation 0 — and its late checkpoint write AND retention
        delete are both REFUSED by the fence, with the refusals in the
        cluster event stream; the generation-1 run's latest_checkpoint
        and training losses are bitwise identical to a twin run the
        zombie never touched."""
        rootA = str(tmp_path / "rootA")
        clusterA = str(tmp_path / "clusterA")
        barrier = str(tmp_path / "barrier")
        os.makedirs(barrier)
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "TF_CPP_MIN_LOG_LEVEL": "2"}
        procs, outs = [], ["", ""]
        for rank in range(2):
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _ZOMBIE_CHILD, rootA, clusterA,
                 barrier, str(tmp_path / f"ev_rank{rank}.jsonl"),
                 str(rank)],
                env=env, cwd=_REPO_ROOT, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        try:
            try:
                outs[0], _ = procs[0].communicate(timeout=240)
            except subprocess.TimeoutExpired:
                pytest.fail("rank 0 never escalated:\n" + outs[0])
            if "STEP 1" not in outs[0]:
                pytest.fail(f"rank 0 never completed a step:"
                            f"\n{outs[0]}")
            # rank 0 escalated after the peer paused
            assert procs[0].returncode == ckpt.ESCALATION_EXIT_CODE, \
                outs[0]
            latest = ckpt.latest_checkpoint(rootA)
            assert latest is not None
            esc_step = ckpt.read_manifest(latest)["step"]
            assert esc_step == 4, esc_step

            # the elastic_run v2 hygiene pass: fence out generation 0.
            # Twin trees let the zombie-exposed run be compared
            # bitwise against a run the zombie can never touch.
            rootB = str(tmp_path / "rootB")
            clusterB = str(tmp_path / "clusterB")
            shutil.copytree(rootA, rootB)
            shutil.copytree(clusterA, clusterB)
            assert cluster.relaunch(clusterA) == 1
            assert cluster.relaunch(clusterB) == 1

            def newgen(root, cdir):
                r = subprocess.run(
                    [sys.executable, "-c", _NEWGEN_CHILD, root, cdir],
                    env=env, cwd=_REPO_ROOT, capture_output=True,
                    text=True, timeout=240)
                assert r.returncode == 0, r.stdout + r.stderr
                return r.stdout.splitlines()

            oracle = newgen(rootB, clusterB)

            # resume the zombie BEFORE the generation-1 run over
            # rootA: its late write/delete race the new epoch and
            # must both be refused
            os.kill(procs[1].pid, signal.SIGCONT)
            outs[1], _ = procs[1].communicate(timeout=240)
            assert procs[1].returncode == 88, outs[1]
            assert "ZOMBIE WRITE FENCED" in outs[1]
            assert "ZOMBIE DELETE FENCED" in outs[1]
            assert "ZOMBIE COMMITTED" not in outs[1]
            assert not os.path.exists(ckpt.step_dir(rootA, 99)), \
                "the zombie's write left debris"

            exposed = newgen(rootA, clusterA)
            assert exposed == oracle, (
                "the zombie changed the generation-1 run:\n"
                f"exposed={exposed}\noracle={oracle}")
            assert exposed[0] == f"RESTORED_STEP {esc_step}"
            assert exposed[-1].startswith("LATEST step_00000103 1")

            # the refusals are ON the zombie's cluster event stream
            ev = open(str(tmp_path / "ev_rank1.jsonl")
                      ).read().splitlines()
            assert not check_cluster_lines(ev)
            fences = [json.loads(l) for l in ev
                      if json.loads(l)["kind"] == "cluster_fence"]
            acts = {f["action"] for f in fences}
            assert acts == {"refused_write", "refused_delete"}, acts
            for f in fences:
                assert f["generation"] == 0
                assert f["current_generation"] == 1
        finally:
            for p in procs:
                if p.poll() is None:
                    try:
                        os.kill(p.pid, signal.SIGCONT)
                    except OSError:
                        pass
                    p.kill()
                    p.wait()


# --- acceptance: coordinated rewind, multi-process, bitwise vs oracle ---------

_COORD_CHILD = textwrap.dedent("""
    import json, os, sys, time
    import jax
    from apex_tpu import _compat
    jax.config.update("jax_platforms", "cpu")
    _compat.request_cpu_devices(4)
    jax.config.update("jax_default_matmul_precision", "highest")

    (imgroot, workdir, cluster_dir, barrier, rank, n_steps,
     poison_step, skip_spec) = sys.argv[1:9]
    rank, n_steps = int(rank), int(n_steps)

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from apex_tpu import ckpt, cluster, guard, monitor
    from apex_tpu.data.pipeline import ImageFolderSource

    IMG, BATCH, LR = 16, 8, 0.002
    mesh = Mesh(np.array(jax.devices()), ("data",))
    shd = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())

    def beat(r, i):
        open(os.path.join(barrier, f"beat_{r}_{i}"), "w").close()

    def wait_peer(r, i):
        p = os.path.join(barrier, f"beat_{r}_{i}")
        while not os.path.exists(p):
            time.sleep(0.02)

    cfg = guard.GuardConfig(window=16, min_history=4, z_threshold=8.0,
                            grad_factor=50.0, lr_growth_interval=3)

    def train_step(params, gs, x, y):
        def loss_fn(p):
            h = x.reshape(x.shape[0], -1) @ p["w"] + p["b"]
            onehot = jax.nn.one_hot(y, p["b"].shape[0],
                                    dtype=jnp.float32)
            return jnp.mean(jnp.square(h - onehot))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        gs = guard.guard_observe(gs, cfg, loss=loss, grads=grads,
                                 params=params)
        new_p = jax.tree_util.tree_map(
            lambda p, g: p - LR * gs.lr_scale * g, params, grads)
        return guard.guard_commit(gs, new_p, params, cfg), gs, loss

    jstep = jax.jit(train_step)

    events = os.path.join(workdir, f"cluster_rank{rank}.jsonl")
    logger = monitor.MetricsLogger(
        sinks=[], cluster_sink=monitor.JSONLSink(events))
    member = cluster.ClusterMembership(
        cluster_dir, rank=rank, ttl_s=60.0,
        event_sink=logger.record_cluster)
    member.join()
    coord = cluster.RecoveryCoordinator(member, barrier_timeout_s=120.0)

    mgr = ckpt.CheckpointManager(
        os.path.join(workdir, f"ck_r{rank}"), fence=member, rank=0,
        process_count=1, keep=0)
    policy = guard.GuardPolicy(manager=mgr, rewind_budget=2)
    src = ImageFolderSource(imgroot, batch=BATCH, size=IMG, seed=3,
                            workers=2, process_index=rank,
                            process_count=2)
    plan = None
    if poison_step:
        plan = guard.FaultPlan(seed=1).add(int(poison_step), "params",
                                           "nan", rank=rank)
    harness = guard.ChaosHarness(plan, rank=rank) if plan else None

    rng = np.random.RandomState(0)      # identical init on both ranks
    params = {
        "w": jax.device_put(jnp.asarray(
            rng.randn(IMG * IMG * 3, 4).astype("float32") * 0.05),
            rep),
        "b": jax.device_put(jnp.zeros((4,), jnp.float32), rep),
    }
    gs = guard.guard_init(cfg)
    it_box = [None]

    def pull():
        while True:
            if it_box[0] is None:
                it_box[0] = src.epoch()
            try:
                return next(it_box[0])
            except StopIteration:
                it_box[0] = None

    if skip_spec:
        skip_at, skip_n = (int(v) for v in skip_spec.split(":"))
    losses, rewound = [], []
    for step in range(n_steps):
        if skip_spec and src.cursor_index() == skip_at:
            src.skip_batches(skip_n)
            it_box[0] = None
        x, y = pull()
        xd = jax.device_put(x, shd)
        yd = jax.device_put(np.asarray(y, np.int32), shd)
        params, gs, loss = jstep(params, gs, xd, yd)
        losses.append(np.float32(np.asarray(loss)))
        if step % 2 == 0:
            mgr.save(step, {"params": params, "gs": gs},
                     extra={"cursor": src.state()})
            mgr.wait()
        member.heartbeat()
        if harness is not None:
            params = harness.post_step(step, params)
        act = policy.update(step, gs)
        assert act.kind != "escalate", act
        need = act.kind == "rewind"
        like = {"params": params, "gs": gs}
        if need:
            # post the intent BEFORE the step barrier, so the healthy
            # peer sees it the moment it crosses — no rank ever runs
            # ahead into the next epoch unaware
            coord.propose(action="rewind", step=step,
                          good_step=policy.probe_good_step(like))
        beat(rank, step)
        wait_peer(1 - rank, step)
        if need or coord.peer_requested():
            dec, restored = coord.run_round(
                policy, step, like, src, expect_ranks=[0, 1],
                reason=act.reason if need else "peer request")
            tree, manifest = restored
            params, gs = tree["params"], tree["gs"]
            it_box[0] = None
            rewound.append((step, dec.target_step, dec.generation,
                            dec.new_generation))
    src.close()
    logger.close()
    out = {
        "losses": [l.tobytes().hex() for l in losses],
        "w": np.asarray(params["w"]).tobytes().hex(),
        "b": np.asarray(params["b"]).tobytes().hex(),
        "rewound": rewound,
        "generation": member.refresh(),
        "final_cursor": src.cursor_index(),
    }
    print("RESULT " + json.dumps(out), flush=True)
""")


def _run_coord_pair(imgroot, workdir, cluster_dir, *, n_steps,
                    poison_step="", skip_spec=""):
    barrier = os.path.join(workdir, "barrier")
    os.makedirs(barrier, exist_ok=True)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "TF_CPP_MIN_LOG_LEVEL": "2"}
    procs = []
    for rank in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _COORD_CHILD, imgroot, workdir,
             cluster_dir, barrier, str(rank), str(n_steps),
             poison_step, skip_spec],
            env=env, cwd=_REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("coordinated run timed out:\n"
                    + "\n---\n".join(outs + ["<pending>"]))
    results = []
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (
            f"rank {rank} failed:\n" + "\n---rank---\n".join(outs))
        line = [l for l in out.splitlines()
                if l.startswith("RESULT ")]
        assert line, out
        results.append(json.loads(line[0][len("RESULT "):]))
    return results


class TestCoordinatedRewindAcceptance:
    @pytest.mark.slow          # 4 subprocess jax bring-ups (~2 min);
    #                            the in-process twin runs in smoke via
    #                            scripts/cluster_audit.py --cpu8
    def test_asymmetric_corruption_resolves_to_common_target(
            self, tmp_path):
        """2 procs × 4 CPU devices, each training its own data shard.
        Chaos poisons rank 1's committed params after step 7 (rank 0
        stays clean). Rank 1's guard detects at step 8, posts a signed
        intent; rank 0 joins the round; both resolve to the SAME
        target (rank 1's newest good step 6 — ckpt@8 captured the
        corruption and probe_good_step rejects it; rank 0 honors the
        cluster verdict over its own good step 8), the generation
        increments EXACTLY once, and both ranks' post-rewind losses
        and final params are bitwise-equal to a fault-free oracle that
        never saw the poison window."""
        from apex_tpu.data.pipeline import make_fake_imagefolder
        imgroot = make_fake_imagefolder(str(tmp_path / "imgs"),
                                        n_classes=4, per_class=8,
                                        size=64, seed=0)
        n = 14
        faulted = _run_coord_pair(
            imgroot, str(tmp_path / "faulted"),
            str(tmp_path / "cluster_f"), n_steps=n, poison_step="7")
        oracle = _run_coord_pair(
            imgroot, str(tmp_path / "oracle"),
            str(tmp_path / "cluster_o"), n_steps=n - 2,
            skip_spec="7:2")

        for rank in (0, 1):
            f, o = faulted[rank], oracle[rank]
            # both ranks agreed on the same round: detected at step 8,
            # target step 6, generation 0 -> 1
            assert f["rewound"] == [[8, 6, 0, 1]], (rank, f["rewound"])
            assert o["rewound"] == []
            # post-rewind steps 9.. replay the oracle's 7.. bitwise
            assert f["losses"][9:] == o["losses"][7:], rank
            assert f["w"] == o["w"] and f["b"] == o["b"], (
                f"rank {rank} final params not bitwise vs oracle")
            assert f["final_cursor"] == o["final_cursor"]
        # the generation incremented exactly once, cluster-wide
        assert cluster.read_generation(
            str(tmp_path / "cluster_f")) == 1
        bump_count = 0
        for rank in (0, 1):
            ev = open(os.path.join(str(tmp_path / "faulted"),
                                   f"cluster_rank{rank}.jsonl")
                      ).read().splitlines()
            assert not check_cluster_lines(ev)
            bump_count += sum(
                1 for l in ev
                if json.loads(l)["kind"] == "cluster_generation"
                and json.loads(l)["action"] == "bump")
        assert bump_count == 1, \
            "the leader alone commits the generation bump"
