"""Set-up shared by the serving drivers: the program's own stack, built
the way ``python -m fmda_tpu serve-fleet`` builds it, and the checks that
hold it to the configuration's guarantees.

    Application(cfg).attach_fleet(model_cfg, params)
        -> FleetGateway.submit / pump -> SessionPool.step_device -> bus

The carrier is derived from the configuration exactly as ``serve-fleet``
does (``bidirectional=False, dropout=0.0``).  Weights are made on the
device in one jitted call from the seed.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmark.harness import schedule as sched
from benchmark.harness.stats import hist_diff

#: Max-abs difference allowed between a served probability and the
#: float32 reference's, by how many ticks the session's state had been
#: carried when the tick was served.  A batched bucket multiplies at the
#: MXU's default precision (one bf16 pass, f32 accumulation), the
#: reference at ``highest``, and the rounding compounds through the
#: carried state.
#:
#: First ``SHORT_CARRY_TICKS`` ticks of a session, 2e-3 (ISSUE 23's):
#: the program's worst there was 6.1e-4 (gru) and 2.8e-4 (ssm) (my chip
#: runs, PR 23; 3.7e-4 and 7.7e-5 in PR 22).  Later ticks, 8e-3: the
#: worst of ~2,000 comparisons a run over sessions carried up to 2,000
#: ticks was 1.5e-3 (gru) and 3.7e-3 (ssm, whose state remembers ~1,000
#: ticks of rounded input projections) (my chip runs, PR 23).
#:
#: What a drop in precision does, so that it still fails: the same
#: carriers computed in bfloat16 throughout (weights, state and
#: arithmetic, in jax.numpy on the same rows, on the chip: my chip run,
#: PR 23) differ from the reference by 9.6e-4 (gru) and 1.4e-3 (ssm)
#: within the first 30 ticks and by 1.8e-2 (gru) and 7.7e-2 (ssm) over
#: 2,000.  It is the long-carry level that fails such a computation, 2x
#: and 10x over; within 30 ticks bf16 and the program are too close to
#: tell apart, and the short-carry level is there so that an error which
#: does not need a long carry to show is not given the long carry's room.
SHORT_CARRY_TICKS = 30
SERVE_TOLERANCE_SHORT = 2e-3
SERVE_TOLERANCE = 8e-3
N_CHECKED_SESSIONS = 8


class Rig:
    """The serving stack of one run, with the bookkeeping the drivers
    share: sessions, warm-up, result collection, window counters."""

    def __init__(self, config: Dict, seed: int, *, trace: bool,
                 parts: Dict[str, float]) -> None:
        t0 = time.perf_counter()
        import jax
        import jax.numpy as jnp

        from fmda_tpu.app import Application
        from fmda_tpu.config import config_from_dict
        from fmda_tpu.models import build_model
        from fmda_tpu.obs.device import configure_device_obs

        self.cfg = config_from_dict(config["framework"])
        configure_device_obs(self.cfg.profiling)
        self.app = Application(self.cfg)
        parts["app_and_native_make"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        self.model_cfg = dataclasses.replace(
            self.cfg.model, bidirectional=False, dropout=0.0)
        model = build_model(self.model_cfg)
        window = self.cfg.runtime.window
        feats = self.model_cfg.n_features
        self.params = jax.jit(lambda key: model.init(
            {"params": key}, jnp.zeros((1, window, feats)))["params"])(
                jax.random.PRNGKey(seed))
        self.gateway = self.app.attach_fleet(self.model_cfg, self.params)
        self.gateway.annotate_device_steps = trace
        jax.block_until_ready(self.gateway.pool.live_tree())
        parts["params_and_pool_build"] = time.perf_counter() - t0
        self.seed = seed
        self.parts = parts
        self.sessions: Optional[sched.Sessions] = None
        #: rows each session was sent before the window, oldest first
        self.warm_rows: Dict[int, List[np.ndarray]] = {}
        self.seq0: Optional[np.ndarray] = None
        self._before = None

    # -- sessions and warm-up ------------------------------------------------

    def open_sessions(self, n_sessions: int) -> None:
        import jax

        from fmda_tpu.data.normalize import NormParams

        t0 = time.perf_counter()
        feats = self.model_cfg.n_features
        self.sessions = s = sched.make_sessions(n_sessions, feats, self.seed)
        for i, sid in enumerate(s.ids):
            self.gateway.open_session(sid, NormParams(s.mins[i], s.maxs[i]))
        jax.block_until_ready(self.gateway.pool.live_tree())
        self.parts["session_opens"] = time.perf_counter() - t0
        self.sid_index = {sid: i for i, sid in enumerate(s.ids)}

    def warm_buckets(self) -> List:
        """Compile (or load) every bucket's program by sending real ticks
        through the gateway — a flush of exactly ``b`` ticks dispatches
        bucket ``b`` — then declare the pool warm: a compile after this
        is counted.  Returns the warm-up's results."""
        t0 = time.perf_counter()
        s = self.sessions
        n = len(s.ids)
        # a flush of min(b, n) ticks dispatches bucket b, unless a smaller
        # bucket already holds that many (a fleet smaller than the bucket)
        sizes, prev = [], 0
        for b in self.cfg.runtime.bucket_sizes:
            if min(b, n) > prev:
                sizes.append(min(b, n))
            prev = b
        session_of_tick = np.concatenate(
            [np.arange(k, dtype=np.int32) for k in sizes])
        rows = sched.walk_rows(s, session_of_tick, self.seed, stream=0)
        results = []
        k = 0
        for size in sizes:
            for i in range(size):
                self.gateway.submit(s.ids[i], rows[k])
                self.warm_rows.setdefault(i, []).append(rows[k])
                k += 1
            results.extend(self.gateway.drain())
        self.gateway.pool.mark_warm()
        self.seq0 = np.array(
            [len(self.warm_rows.get(i, ())) for i in range(n)], np.int64)
        self.parts["bucket_warmup"] = time.perf_counter() - t0
        return results

    # -- window bookkeeping --------------------------------------------------

    def snapshot(self) -> Dict:
        m = self.gateway.metrics
        return {
            "hist": {s: h.snapshot() for s, h in m.histograms.items()},
            "counters": dict(m.counters),
        }

    def window_begin(self) -> None:
        self._before = self.snapshot()

    def window_end(self) -> Dict:
        """Counters and stage histograms of the window alone."""
        after = self.snapshot()
        b = self._before
        counters = {k: v - b["counters"].get(k, 0)
                    for k, v in after["counters"].items()}
        hist = {s: hist_diff(after["hist"][s], b["hist"].get(s))
                for s in after["hist"]}
        return {"counters": counters, "hist": hist}

    # -- correctness ---------------------------------------------------------

    def checked_sessions(self, ticks_per_session: np.ndarray) -> List[int]:
        """Eight sessions fixed by the traffic: the hottest, the coldest
        that ticked at all, and six evenly between."""
        ranked = np.argsort(-ticks_per_session, kind="stable")
        ranked = ranked[ticks_per_session[ranked] > 0]
        if len(ranked) <= N_CHECKED_SESSIONS:
            return [int(i) for i in ranked]
        picks = np.linspace(0, len(ranked) - 1, N_CHECKED_SESSIONS)
        return [int(ranked[int(round(p))]) for p in picks]

    def check_against_reference(
        self, rows_by_session: Dict[int, np.ndarray],
        seqs_by_session: Dict[int, Sequence[int]],
        served: Dict[int, Dict[int, np.ndarray]],
    ) -> Dict:
        """Feed the reference each checked session's rows — the warm-up's
        first, then the window's in send order, leaving out ticks the
        gateway shed (their rows never reached the model) — and compare
        every probability the run returned for it.  ``seqs_by_session``
        gives the seq of each of ``rows_by_session``'s rows."""
        from benchmark.reference.serving import BY_CELL

        ref_fn = BY_CELL[self.model_cfg.cell]
        s = self.sessions
        worst_short = worst_long = 0.0
        n_short = n_long = 0
        for i, rows in rows_by_session.items():
            warm = self.warm_rows.get(i, [])
            all_rows = np.concatenate(
                [np.asarray(warm, np.float32).reshape(
                    -1, rows.shape[1]), rows])
            seqs = list(range(len(warm))) + list(seqs_by_session[i])
            ref = ref_fn(self.params, all_rows, s.mins[i], s.maxs[i],
                         self.cfg.runtime.window)
            got = served.get(i, {})
            # row j is the session's j-th tick to reach the model: its
            # state had been carried j ticks
            for j, seq in enumerate(seqs):
                if seq not in got:
                    continue
                err = float(np.max(np.abs(ref[j] - got[seq])))
                if j < SHORT_CARRY_TICKS:
                    worst_short = max(worst_short, err)
                    n_short += 1
                else:
                    worst_long = max(worst_long, err)
                    n_long += 1
        return {"max_abs_err": max(worst_short, worst_long),
                "max_abs_err_short_carry": worst_short,
                "max_abs_err_long_carry": worst_long,
                "compared": n_short + n_long,
                "compared_short_carry": n_short,
                "short_carry_ticks": SHORT_CARRY_TICKS,
                "tolerance_short_carry": SERVE_TOLERANCE_SHORT,
                "tolerance": SERVE_TOLERANCE,
                "ok": (n_short + n_long > 0
                       and worst_short <= SERVE_TOLERANCE_SHORT
                       and worst_long <= SERVE_TOLERANCE)}

    def served_so_far(self, checked, warm_results) -> Dict:
        """Per checked session, seq -> probabilities, seeded with what the
        warm-up returned (its ticks are part of each session's stream)."""
        served: Dict[int, Dict[int, np.ndarray]] = {i: {} for i in checked}
        for r in warm_results:
            i = self.sid_index[r.session_id]
            if i in served:
                served[i][r.seq] = r.probabilities
        return served

    def compile_facts(self) -> Dict:
        pool = self.gateway.pool
        return {
            "compile_count": pool.compile_count,
            "recompiles_after_warmup": pool.recompiles_after_warmup,
        }

    def verdict(self, counters: Dict, failed: int, reference: Dict,
                seq_contiguous: bool, **more) -> Dict:
        """The guarantees a serving run is held to, from the window's
        counters: every unanswered tick is a counted shed, loss or stale
        drop; results in order; nothing compiled after warm-up; one
        program per bucket dispatched; the reference agrees."""
        shed = int(counters.get("shed_oldest", 0)
                   + counters.get("quota_shed", 0))
        lost = int(counters.get("flush_results_lost", 0))
        stale = int(counters.get("stale_dropped", 0)
                    + counters.get("stale_results_dropped", 0))
        facts = self.compile_facts()
        buckets = sorted(
            int(k.rsplit("_", 1)[1])
            for k, v in self.gateway.metrics.counters.items()
            if k.startswith("flushes_bucket_") and v > 0)
        checks = {
            "reference": reference,
            "counts_balance": failed == shed + lost + stale,
            "failed_breakdown": {"unanswered": failed, "shed": shed,
                                 "lost": lost, "stale_dropped": stale},
            "seq_contiguous": seq_contiguous,
            "recompiles_after_warmup": facts["recompiles_after_warmup"],
            "compile_count": facts["compile_count"],
            "buckets_dispatched": buckets,
            "compile_count_matches": facts["compile_count"] == len(buckets),
            **more,
        }
        checks["correct"] = bool(
            reference["ok"] and checks["counts_balance"] and seq_contiguous
            and facts["recompiles_after_warmup"] == 0
            and checks["compile_count_matches"])
        return checks

    def close(self) -> None:
        self.app.close()
