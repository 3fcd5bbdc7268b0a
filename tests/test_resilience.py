"""Chaos suite for the fault-tolerant async runtime (repro.resilience).

Seeded fault plans drive every scenario, so each test is exactly
reproducible: crash-consistent checkpoints (torn-pair detection,
newest-valid fallback), bit-exact crash->resume parity, supervised
worker restarts with zero trainer deadlock, on-device non-finite guards,
weight-publish retries, and serving graceful degradation (KV-pool shed,
NaN-logit quarantine).
"""
import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

import jax
import numpy as np
import pytest

from repro.async_rl.buffer import QueueClosed, RolloutQueue
from repro.async_rl.weights import WeightStore
from repro.configs.base import RLConfig
from repro.configs.registry import get_config
from repro.data.tasks import ArithmeticTask
from repro.resilience import (
    CheckpointManager,
    DivergenceDetector,
    FaultPlan,
    FaultSpec,
    InjectedFault,
    PublishError,
    ResilienceConfig,
    ResilientPublisher,
    SupervisedWorker,
    TrainGuard,
    WorkerFailed,
    parse_fault,
    pop_with_health,
)
from repro.rollout.engine import RolloutBatch
from repro.training.checkpoints import (
    CheckpointError,
    load_checkpoint,
    save_checkpoint,
)
from repro.training.trainer import Trainer, assemble_train_batch


@pytest.fixture(scope="module")
def toy():
    return dataclasses.replace(get_config("toy-2m"), dtype="float32")


@pytest.fixture(scope="module")
def rl():
    return RLConfig(group_size=2, num_minibatches=1, learning_rate=2e-4,
                    max_staleness=3)


def _task():
    return ArithmeticTask(max_operand=9, n_terms=2, prompt_len=8, seed=0)


def _mk_batch(version):
    return RolloutBatch(np.zeros((1, 4), np.int32), np.array([2]),
                        np.zeros((1, 2), np.float32),
                        np.ones((1, 2), np.float32), version=version)


# ------------------------------------------------------------- fault plane
class TestFaultPlan:
    def test_parse_grammar(self):
        s = parse_fault("rollout_crash@3")
        assert (s.kind, s.at, s.times, s.magnitude) == \
            ("rollout_crash", 3, 1, 0.0)
        s = parse_fault("kv_exhaust@5x3:64")
        assert (s.kind, s.at, s.times, s.magnitude) == \
            ("kv_exhaust", 5, 3, 64.0)
        s = parse_fault("queue_stall@2:0.25")
        assert (s.kind, s.at, s.times, s.magnitude) == \
            ("queue_stall", 2, 1, 0.25)
        assert parse_fault(s.spec_str()) == s

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_fault("no_at_sign")
        with pytest.raises(ValueError):
            parse_fault("unknown_kind@0")
        with pytest.raises(ValueError):
            FaultSpec("train_crash", at=-1)
        with pytest.raises(ValueError):
            FaultSpec("train_crash", at=0, times=0)

    def test_occurrence_window(self):
        plan = FaultPlan([FaultSpec("train_crash", at=2, times=2)])
        hits = [plan.check("train_crash") is not None for _ in range(6)]
        assert hits == [False, False, True, True, False, False]
        assert plan.occurrences("train_crash") == 6
        assert [f["occurrence"] for f in plan.fired] == [2, 3]

    def test_sites_are_independent(self):
        plan = FaultPlan([FaultSpec("nan_grad", at=0)])
        assert plan.check("rollout_crash") is None  # different site
        assert plan.check("nan_grad") is not None

    def test_maybe_crash_raises(self):
        plan = FaultPlan.from_strings(["train_crash@1"])
        plan.maybe_crash("train_crash")  # occurrence 0: healthy
        with pytest.raises(InjectedFault) as ei:
            plan.maybe_crash("train_crash")
        assert ei.value.occurrence == 1

    def test_seeded_rng_deterministic(self):
        a = FaultPlan([], seed=7).rng.integers(1000, size=5)
        b = FaultPlan([], seed=7).rng.integers(1000, size=5)
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------ atomic checkpoints
class TestAtomicCheckpoint:
    def test_roundtrip_and_checksum(self, tmp_path):
        path = str(tmp_path / "ck")
        tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
                "nested": {"b": np.ones((3,), np.float32)}}
        save_checkpoint(path, tree, {"step": 4})
        out, meta = load_checkpoint(path)
        assert meta == {"step": 4}  # format keys stripped
        np.testing.assert_array_equal(out["w"], tree["w"])
        # no staging litter left behind
        assert not [n for n in os.listdir(tmp_path)
                    if n.startswith(".ckpt-tmp")]

    def test_torn_npz_detected(self, tmp_path):
        path = str(tmp_path / "ck")
        save_checkpoint(path, {"w": np.ones((8, 8), np.float32)}, {})
        with open(path + ".npz", "r+b") as f:
            f.seek(60)
            f.write(b"\xde\xad\xbe\xef")
        with pytest.raises(CheckpointError, match="checksum"):
            load_checkpoint(path)

    def test_missing_pieces_detected(self, tmp_path):
        path = str(tmp_path / "ck")
        save_checkpoint(path, {"w": np.ones(3, np.float32)}, {})
        os.unlink(path + ".json")
        with pytest.raises(CheckpointError):
            load_checkpoint(path)
        with pytest.raises(CheckpointError):
            load_checkpoint(str(tmp_path / "never-saved"))


class TestCheckpointManager:
    def test_save_restore_full_capture(self, toy, rl, tmp_path):
        trainer = Trainer(toy, rl)
        state = trainer.init_state(jax.random.PRNGKey(0))
        task = _task()
        task.sample(3)  # advance the RNG so the state is non-trivial
        mgr = CheckpointManager(str(tmp_path))
        key = jax.random.PRNGKey(42)
        mgr.save(2, state, key=key,
                 history=[(state.params, 0)],
                 task_rng_state=task.rng.bit_generator.state,
                 extra={"algo": "a3po"})
        info = mgr.restore_latest()
        assert info is not None and info.step == 2
        assert info.metadata["algo"] == "a3po"
        np.testing.assert_array_equal(np.asarray(info.key), np.asarray(key))
        assert len(info.history) == 1 and info.history[0][1] == 0
        # restored task RNG continues the same stream
        fresh = _task()
        fresh.rng.bit_generator.state = info.task_rng_state
        np.testing.assert_array_equal(fresh.sample(2).prompts,
                                      task.sample(2).prompts)
        for a, b in zip(jax.tree.leaves(state.params),
                        jax.tree.leaves(info.state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_pointer_and_retention(self, toy, rl, tmp_path):
        state = Trainer(toy, rl).init_state(jax.random.PRNGKey(0))
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for step in (1, 2, 3, 4):
            mgr.save(step, state)
        assert mgr.latest_step() == 4
        kept = sorted(n for n in os.listdir(tmp_path)
                      if n.endswith(".json") and n != "latest")
        assert kept == ["step_00000003.json", "step_00000004.json"]

    def test_corrupt_newest_falls_back(self, toy, rl, tmp_path):
        state = Trainer(toy, rl).init_state(jax.random.PRNGKey(0))
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, state)
        mgr.save(2, state)
        # tear the newest checkpoint's npz (simulated mid-write crash)
        with open(mgr.path_for(2) + ".npz", "r+b") as f:
            f.seek(40)
            f.write(b"\x00" * 16)
        info = mgr.restore_latest()
        assert info is not None and info.step == 1

    def test_empty_dir_returns_none(self, tmp_path):
        assert CheckpointManager(str(tmp_path)).restore_latest() is None


# ------------------------------------------------------------ rollout queue
class TestRolloutQueueTimeouts:
    def test_pop_timeout_raises(self):
        q = RolloutQueue(capacity=2, max_staleness=2)
        with pytest.raises(TimeoutError):
            q.pop(timeout=0.05)
        with pytest.raises(TimeoutError):
            q.pop_fresh(current_version=0, n=1, timeout=0.05)

    def test_close_wakes_blocked_consumer(self):
        q = RolloutQueue(capacity=2, max_staleness=2)
        err = []

        def consumer():
            try:
                q.pop(timeout=30.0)
            except QueueClosed as e:
                err.append(e)

        t = threading.Thread(target=consumer)
        t.start()
        time.sleep(0.05)
        q.close()
        t.join(timeout=5.0)
        assert not t.is_alive() and len(err) == 1

    def test_closed_queue_still_drains_pending(self):
        q = RolloutQueue(capacity=2, max_staleness=2)
        q.push(_mk_batch(0))
        q.close()
        assert q.pop(timeout=0.5).version == 0
        with pytest.raises(QueueClosed):
            q.pop(timeout=0.5)
        with pytest.raises(QueueClosed):
            q.push(_mk_batch(1))

    def test_pop_fresh_deadline_spans_stale_drops(self):
        """Stale batches must not reset the clock: the whole call is
        bounded by one deadline."""
        q = RolloutQueue(capacity=4, max_staleness=1)
        q.push(_mk_batch(0))  # stale at current_version=5
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError):
            q.pop_fresh(current_version=5, n=1, timeout=0.2)
        assert time.perf_counter() - t0 < 5.0
        assert q.dropped == 1


# --------------------------------------------------------------- supervisor
class TestSupervisedWorker:
    def test_crash_restart_then_succeed(self):
        calls = []

        def body(ctx):
            calls.append(1)
            if len(calls) < 3:
                raise RuntimeError("boom")
            while not ctx.should_stop():
                ctx.heartbeat()
                time.sleep(0.01)

        w = SupervisedWorker("t", body, max_restarts=5,
                             backoff_base_s=0.01, backoff_max_s=0.02)
        w.start()
        time.sleep(0.5)
        assert w.alive and not w.failed
        assert w.restarts == 2 and len(w.crashes) == 2
        assert w.health_error() is None
        assert w.crashes[0].recovery_s >= 0.0  # MTTR sample recorded
        w.stop()
        assert not w.alive

    def test_budget_exhaustion_flags_failed(self):
        def body(ctx):
            raise ValueError("always broken")

        w = SupervisedWorker("t", body, max_restarts=2,
                             backoff_base_s=0.005, backoff_max_s=0.01)
        w.start()
        deadline = time.time() + 5.0
        while not w.failed and time.time() < deadline:
            time.sleep(0.01)
        assert w.failed and w.restarts == 2 and len(w.crashes) == 3
        assert "failed permanently" in w.health_error()
        assert w.last_crash.exc_type == "ValueError"

    def test_pop_with_health_never_deadlocks_on_dead_producer(self):
        """Regression: a killed worker used to leave the trainer blocked
        in queue.pop forever. Now the consumer raises WorkerFailed."""
        q = RolloutQueue(capacity=2, max_staleness=2)

        def body(ctx):
            raise RuntimeError("producer died instantly")

        w = SupervisedWorker("dead", body, max_restarts=0)
        w.start()
        t0 = time.perf_counter()
        with pytest.raises(WorkerFailed, match="failed permanently"):
            pop_with_health(q, w, current_version=0, poll_s=0.05,
                            deadline_s=30.0)
        assert time.perf_counter() - t0 < 10.0
        w.stop()

    def test_pop_with_health_deadline(self):
        q = RolloutQueue(capacity=2, max_staleness=2)
        with pytest.raises(TimeoutError):
            pop_with_health(q, None, current_version=0, poll_s=0.05,
                            deadline_s=0.15)


# ------------------------------------------------------------------- guards
class TestGuards:
    def test_divergence_detector(self):
        det = DivergenceDetector(window=8, threshold_sigmas=4.0,
                                 min_window=4)
        for _ in range(8):
            assert not det.update(1.0 + 0.01 * np.random.default_rng(0)
                                  .standard_normal())
        assert det.update(100.0)
        assert det.update(float("nan"))

    def test_divergence_detector_ignores_noise(self):
        """A 1 % noisy window, then steps within 3 sigma of it: no trip,
        the sigma floor stays under real noise."""
        det = DivergenceDetector(window=8, threshold_sigmas=4.0,
                                 min_window=4)
        window = 1.0 + 0.01 * np.random.default_rng(0).standard_normal(8)
        for x in window:
            assert not det.update(float(x))
        mean, std = float(window.mean()), float(window.std())
        for k in (1.0, 2.0, 3.0):
            assert not det.update(mean + k * std)

    def test_guard_policies(self):
        g = TrainGuard(policy="skip")
        ok = g.after_step({"loss": 1.0, "nonfinite": 0.0})
        assert ok.action == "ok"
        v = g.after_step({"loss": float("nan"), "nonfinite": 2.0})
        # counts skipped *minibatches*, not steps
        assert v.action == "skip" and g.skipped_updates == 2
        g2 = TrainGuard(policy="rollback")
        v2 = g2.after_step({"loss": float("nan"), "nonfinite": 1.0})
        assert v2.action == "rollback" and g2.rollbacks == 1

    def test_on_device_skip_keeps_params_bit_identical(self, toy, rl):
        """A NaN reward poisons loss + every grad leaf; with the guard the
        packed-metrics step must leave params and Adam state exactly
        unchanged (jnp.where on device, no extra host sync) and count the
        skipped minibatch. Without it, params go non-finite."""
        from repro.rollout.engine import RolloutEngine
        task = _task()
        engine = RolloutEngine(toy, rl, max_new_tokens=3)
        guarded = Trainer(toy, rl, "loglinear", skip_nonfinite=True)
        state = guarded.init_state(jax.random.PRNGKey(0))
        batch = task.sample(2)
        prompts = np.repeat(batch.prompts, rl.group_size, axis=0)
        lengths = np.repeat(batch.prompt_lengths, rl.group_size)
        rb = engine.generate(state.params, prompts, lengths,
                             jax.random.PRNGKey(1), version=0)
        rewards = np.full((prompts.shape[0],), np.nan, np.float32)
        tb = assemble_train_batch([rb], rewards)

        before = [np.asarray(x) for x in jax.tree.leaves(state.params)]
        opt_before = [np.asarray(x) for x in jax.tree.leaves(state.opt)]
        state2, m = guarded.step(state, tb)
        assert m["nonfinite"] >= 1.0
        for a, b in zip(before, jax.tree.leaves(state2.params)):
            np.testing.assert_array_equal(a, np.asarray(b))
        for a, b in zip(opt_before, jax.tree.leaves(state2.opt)):
            np.testing.assert_array_equal(a, np.asarray(b))

        unguarded = Trainer(toy, rl, "loglinear")
        state3, m3 = unguarded.step(state, tb)
        assert not all(np.isfinite(np.asarray(leaf)).all()
                       for leaf in jax.tree.leaves(state3.params))


# ------------------------------------------------------------ sim chaos
class TestSimulatorChaos:
    def test_crash_resume_bit_exact(self, toy, rl, tmp_path):
        """Kill mid-training at a fault-plan step; `--resume auto`
        semantics restore params/opt/RNG/staleness-history and the run
        finishes bit-identical to an uninterrupted one."""
        from repro.async_rl.orchestrator import simulate_async
        steps, every, crash_at = 6, 2, 5

        res_a = ResilienceConfig(
            checkpointer=CheckpointManager(str(tmp_path / "a")),
            ckpt_every=every)
        state_a, recs_a = simulate_async(
            toy, rl, _task(), "loglinear", steps, n_prompts=2,
            max_new_tokens=3, staleness=1, seed=0, resilience=res_a)
        assert recs_a[-1].resilience[
            "resilience_checkpoint_saves_total"] >= 3

        res_b = ResilienceConfig(
            checkpointer=CheckpointManager(str(tmp_path / "b")),
            ckpt_every=every,
            faults=FaultPlan.from_strings([f"train_crash@{crash_at}"]))
        with pytest.raises(InjectedFault):
            simulate_async(toy, rl, _task(), "loglinear", steps,
                           n_prompts=2, max_new_tokens=3, staleness=1,
                           seed=0, resilience=res_b)

        res_c = ResilienceConfig(
            checkpointer=CheckpointManager(str(tmp_path / "b")),
            ckpt_every=every)
        resume = res_c.checkpointer.restore_latest()
        assert resume is not None and resume.step == 4
        state_b, recs_b = simulate_async(
            toy, rl, _task(), "loglinear", steps, n_prompts=2,
            max_new_tokens=3, staleness=1, seed=0, resilience=res_c,
            resume=resume)
        assert [r.step for r in recs_b] == [4, 5]
        for a, b in zip(jax.tree.leaves(state_a.params),
                        jax.tree.leaves(state_b.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(state_a.opt),
                        jax.tree.leaves(state_b.opt)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_nan_grad_fault_with_guard(self, toy, rl):
        from repro.async_rl.orchestrator import simulate_async
        res = ResilienceConfig(
            faults=FaultPlan.from_strings(["nan_grad@1"]),
            guard=TrainGuard(policy="skip"))
        state, recs = simulate_async(
            toy, rl, _task(), "loglinear", 3, n_prompts=2,
            max_new_tokens=3, staleness=1, seed=0, resilience=res)
        assert res.guard.skipped_updates == 1
        assert all(np.isfinite(np.asarray(x)).all()
                   for x in jax.tree.leaves(state.params))
        snap = recs[-1].resilience
        assert snap['resilience_faults_injected_total{kind="nan_grad"}'] \
            >= 1.0


# ------------------------------------------------------- async orchestrator
class TestAsyncChaos:
    def test_rollout_crash_restarted_no_deadlock(self, toy, rl):
        """An injected rollout-worker crash is restarted by the
        supervisor; the trainer never deadlocks and every step completes.
        Fault + restart counters surface in StepRecord.resilience."""
        from repro.async_rl.orchestrator import AsyncOrchestrator
        res = ResilienceConfig(
            faults=FaultPlan.from_strings(["rollout_crash@1"]),
            max_worker_restarts=3, pop_deadline_s=60.0)
        orch = AsyncOrchestrator(toy, rl, _task(), "loglinear",
                                 n_prompts=2, max_new_tokens=3,
                                 queue_capacity=2, resilience=res)
        trainer = Trainer(toy, rl, "loglinear")
        state = trainer.init_state(jax.random.PRNGKey(0))
        state, recs = orch.run(state, num_steps=3)
        assert len(recs) == 3 and int(state.version) == 3
        assert len(orch.worker.crashes) == 1
        assert orch.worker.restarts == 1 and not orch.worker.failed
        snap = recs[-1].resilience
        assert snap["resilience_worker_restarts_total"] >= 1.0
        assert snap[
            'resilience_faults_injected_total{kind="rollout_crash"}'] >= 1.0
        assert orch.queue.closed  # clean shutdown propagated

    def test_dead_producer_surfaces_worker_failed(self, toy, rl):
        """Worker crashes past its restart budget -> the trainer's pop
        raises WorkerFailed promptly instead of hanging."""
        from repro.async_rl.orchestrator import AsyncOrchestrator
        res = ResilienceConfig(
            faults=FaultPlan.from_strings(["rollout_crash@0x16"]),
            max_worker_restarts=1, pop_deadline_s=60.0)
        orch = AsyncOrchestrator(toy, rl, _task(), "loglinear",
                                 n_prompts=2, max_new_tokens=3,
                                 queue_capacity=2, resilience=res)
        state = Trainer(toy, rl, "loglinear").init_state(
            jax.random.PRNGKey(0))
        t0 = time.perf_counter()
        with pytest.raises(WorkerFailed):
            orch.run(state, num_steps=2)
        assert time.perf_counter() - t0 < 60.0
        assert orch.worker.failed


# ----------------------------------------------------------------- publish
class TestPublishResilience:
    def test_retry_then_recover(self, toy):
        from repro.models import model as M
        params = M.init_params(toy, jax.random.PRNGKey(0))
        store = WeightStore(params, 0)
        pub = ResilientPublisher(
            store, faults=FaultPlan.from_strings(["publish_fail@0x2"]),
            max_retries=5, backoff_base_s=0.001, backoff_max_s=0.002)
        attempts = pub.publish(params, 1)
        assert attempts == 3 and store.version == 1
        assert pub.retries == 2 and pub.failures == 0

    def test_budget_exhausted_raises_store_untouched(self, toy):
        from repro.models import model as M
        params = M.init_params(toy, jax.random.PRNGKey(0))
        store = WeightStore(params, 0)
        pub = ResilientPublisher(
            store, faults=FaultPlan.from_strings(["publish_fail@0x99"]),
            max_retries=2, backoff_base_s=0.001, backoff_max_s=0.002)
        with pytest.raises(PublishError):
            pub.publish(params, 1)
        # old version keeps serving — the store never saw the new one
        assert store.version == 0 and pub.failures == 1


# ---------------------------------------------------- serving degradation
class TestServingDegradation:
    def _cp(self, cfg, params, *, faults=None, n_blocks=16, max_seqs=2,
            max_new=8):
        from repro.rollout.continuous import ContinuousBatchingEngine
        from repro.serving import (AdmissionScheduler, SchedulerConfig,
                                   ServingControlPlane)
        eng = ContinuousBatchingEngine(
            cfg, max_seqs=max_seqs, block_size=4, n_blocks=n_blocks,
            max_blocks_per_seq=8, greedy=True)
        cp = ServingControlPlane(
            eng, WeightStore(params, 0),
            AdmissionScheduler(SchedulerConfig(d_max=100,
                                               max_preempts=100)),
            use_prefix_cache=False, faults=faults)
        return eng, cp

    def test_kv_exhaust_sheds_instead_of_oom(self, toy):
        """Starve the block pool mid-decode: the control plane sheds a
        sequence through the scheduler (and later finishes it) instead of
        the allocator raising mid-CoW-fork.

        The engine pre-maps a sequence's full extent at admission, so the
        only organic decode-time allocation is a copy-on-write fork of a
        radix-shared write block. We set up exactly that state — an extra
        reference on the next write block, as the prefix cache holds on
        matched prompt blocks — while the kv_exhaust fault takes the free
        pool hostage."""
        from repro.models import model as M
        from repro.rollout import paged_cache as pc
        params = M.init_params(toy, jax.random.PRNGKey(0))
        faults = FaultPlan.from_strings(["kv_exhaust@3x5:99"])
        eng, cp = self._cp(toy, params, faults=faults, n_blocks=13)
        rng = np.random.default_rng(0)
        for _ in range(2):
            cp.submit(rng.integers(4, toy.vocab_size, 12).astype(np.int32),
                      max_new=8)
        key = jax.random.PRNGKey(0)
        done = 0
        for _ in range(3):  # warm up: both sequences mid-generation
            key, sub = jax.random.split(key)
            done += len(cp.step(sub))
        assert done == 0
        # mimic a radix-shared write block on slot 0: its next decode
        # write needs a CoW fork (1 fresh block) — but the fault is about
        # to grab the entire free pool
        first, _ = pc.write_range(int(eng._lens[0]), 1,
                                  eng.state.block_size, eng.state.max_blocks)
        eng.allocator.incref(int(eng._tables[0, first]))
        for _ in range(200):
            key, sub = jax.random.split(key)
            done += len(cp.step(sub))
            if done == 2:
                break
        assert done == 2                      # everything still finishes
        assert cp.metrics.oom_sheds >= 1      # via the shed path
        assert cp._kv_holds == []             # fault released its hostages

    def test_nan_logits_quarantined(self, toy):
        """A poisoned decode row must never leak non-finite logprobs into
        rollout data: the finished request is dropped + resubmitted."""
        from repro.models import model as M
        params = M.init_params(toy, jax.random.PRNGKey(0))
        # max_seqs=1 -> the poisoned row is always the active slot
        faults = FaultPlan.from_strings(["nan_logits@1"])
        eng, cp = self._cp(toy, params, faults=faults, n_blocks=32,
                           max_seqs=1)
        prompt = np.random.default_rng(0).integers(
            4, toy.vocab_size, 8).astype(np.int32)
        rid = cp.submit(prompt, max_new=4)
        key = jax.random.PRNGKey(0)
        for _ in range(200):
            key, sub = jax.random.split(key)
            finished = cp.step(sub)
            if finished:
                break
        assert cp.metrics.nan_drops >= 1
        req = finished[0]
        assert req.rid == rid
        assert np.isfinite(np.asarray(req.gen_logp, np.float64)).all()
        rb = cp.rollout_batch([req], prompt_pad=8, max_new=4)
        assert np.isfinite(rb.gen_logp).all()


# ------------------------------------------- sharded restore on a real mesh
_SHARDED_SCRIPT = textwrap.dedent("""
    import dataclasses, json, os, sys
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.configs.registry import get_config
    from repro.distributed.sharding import ShardingEnv, use_sharding
    from repro.models import model as M
    from repro.training.checkpoints import restore_sharded, save_checkpoint

    cfg = dataclasses.replace(get_config("toy-2m"), dtype="float32")
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    env = ShardingEnv(mesh)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    shardings = M.param_shardings(cfg, env)
    path = sys.argv[1]
    with mesh, use_sharding(env):
        save_checkpoint(path, params, {"arch": "toy-2m", "v": 9})
        restored, meta = restore_sharded(path, shardings)
    assert meta["v"] == 9
    n_sharded = 0
    for (kp, leaf), sh in zip(
            jax.tree_util.tree_flatten_with_path(restored)[0],
            jax.tree.leaves(shardings)):
        assert leaf.sharding == sh, (kp, leaf.sharding, sh)
        if len(leaf.shape) >= 2 and not sh.is_fully_replicated:
            n_sharded += 1
    orig = jax.tree.leaves(params)
    for a, b in zip(orig, jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print(json.dumps({"n_devices": jax.device_count(),
                      "n_sharded_weights": n_sharded}))
""")


def test_restore_sharded_on_multidevice_mesh(tmp_path):
    """Checkpoint roundtrip + ``restore_sharded`` against the production
    mesh spec (ShardingEnv logical-axis rules) on an 8-device host
    platform: every leaf lands on its mesh sharding, weights actually
    sharded, values bit-exact. Runs in a subprocess because XLA_FLAGS
    must be set before the first jax import."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT, str(tmp_path / "ck")],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 8
    assert out["n_sharded_weights"] > 0
