"""Path driver ``service_prefill``: requests that climb the stage ladder.

One window serves the cell's open-loop stream through ``Service`` on the
wall clock: the ``rtdeepiot`` policy, the ``device-kernel`` executor in
classifier mode (stage trunk plus the fused exit kernel, compiled), batch
buckets and length buckets.  Set-up makes the weights,
compiles and warms every (stage, batch bucket, length bucket) shape the
traffic uses, and takes the scheduler's WCET profile, which the program
prices its deadlines with.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import instrument, traffic as traffic_mod


class PrefillCell:
    def __init__(self, m: dict, tr: dict, cfg, *, interpret=None):
        self.m, self.tr, self.cfg = m, tr, cfg
        self.interpret = interpret
        self.params = None
        self.fns = None
        self.tm = None
        self.host_overhead = 0.0
        self.wcet = None

    # -- set-up -----------------------------------------------------------
    def sample_input(self, length: int):
        return {"tokens": np.zeros((1, length), np.int32)}

    def len_buckets(self):
        return list(self.tr["len_buckets"])

    def setup(self, params) -> None:
        """Compile and profile every shape of the cell's traffic."""
        from repro.launch.kernel import KernelStageFns
        from repro.serving import profile_batched_stages, profile_host_overhead
        from repro.serving.batch.time_model import LengthBucketTimeModel
        self.params = params
        buckets = tuple(self.tr["batch_buckets"])
        self.fns = KernelStageFns(self.cfg, buckets, interpret=self.interpret)
        mats = []
        for lb in self.len_buckets():
            _tm, mat = profile_batched_stages(
                self.cfg, params, self.fns, self.sample_input(lb),
                n_runs=int(self.tr["wcet_runs"]))
            mats.append(mat)
        self.wcet = np.asarray(mats)           # (len buckets, stages, buckets)
        self.tm = LengthBucketTimeModel.from_profile3(
            mats, buckets, self.len_buckets())
        self.host_overhead = float(profile_host_overhead(n_runs=50))
        for lb in self.len_buckets():
            for b in buckets:
                self._warm_serving_calls(self.sample_input(lb), b)

    def _warm_serving_calls(self, sample, b: int) -> None:
        """Make the calls the window makes, at one (batch, length) shape:
        the stage fns fed from the staging buffers (host arrays, another
        compiled variant than the profile's device arrays) and the
        executor's per-row slicing of each stage's output."""
        import jax
        rows = [sample] * b
        for s in range(self.cfg.num_stages):
            h_out, pred, conf, _mask = self.fns.run(s, self.params, rows)
            jax.block_until_ready(h_out)
            np.asarray(pred), np.asarray(conf)
            rows = [jax.tree.map(lambda x, k=k: x[k:k + 1], h_out)
                    for k in range(b)]
        jax.block_until_ready(rows)

    # -- one window -------------------------------------------------------
    def requests(self, seconds: float, seed: int) -> list:
        return traffic_mod.make_requests(self.tr, seconds, seed,
                                         vocab=self.m["vocab_size"])

    def _stream(self, reqs):
        from repro.serving.engine import Request
        return [(r["offset"], Request(inputs={"tokens": r["inputs"][None]},
                                      rel_deadline=r["rel"],
                                      sample=r["index"], seq_len=r["seq_len"]))
                for r in reqs]

    def serve(self, reqs, *, trace: bool) -> dict:
        """Serve ``reqs`` through ``Service``; returns what the window left:
        per-request records, times, executor counters and, when traced,
        the dispatch log and scheduler timing."""
        from repro.launch.kernel import build_kernel_executor
        from repro.serving import ServeSpec, Service
        from repro.serving.registry import resolve
        from repro.serving.runtime.clock import WallClock
        import repro.launch.serve  # noqa: F401  (registers device-kernel)
        import types

        import jax

        pol = self.tr["policy"]
        ex_args = {"len_buckets": self.len_buckets()}
        spec = ServeSpec(
            policy=pol["name"], policy_args=pol.get("args", {}),
            executor="device-kernel", executor_args=ex_args, clock="wall",
            source="stream", batching={}, host_overhead=self.host_overhead,
            pipeline_depth=int(self.tr["pipeline_depth"]),
            trace={"enabled": True} if trace else {})
        spec.validate()
        clock = instrument.window_clock(WallClock, annotate_sleep=trace)
        res = {"cfg": self.cfg, "params": self.params, "time_model": self.tm,
               "clock": clock}
        fns = self.fns
        if trace:
            fns = instrument.RecordingStageFns(
                self.fns, self.tr["batch_buckets"],
                lambda t: int(jax.tree.leaves(t)[0].shape[1]))
            base = resolve("policy", pol["name"])(pol.get("args", {}), None)
            res["policy"] = instrument.TimedPolicy(base)
        res["stage_fns"] = fns
        ctx = types.SimpleNamespace(resources=res, time_model=self.tm,
                                    spec=spec)
        ex = build_kernel_executor(ex_args, ctx)
        if trace:
            instrument.annotate_method(ex, "complete", "perfbench.wait_device")
            instrument.annotate_method(ex, "commit", "perfbench.commit")
        res["executor"] = ex
        svc = Service.from_spec(spec, res)
        try:
            met = svc.run(self._stream(reqs))
        finally:
            end = time.perf_counter()
            clock.span.close()
        out = {
            "records": list(met.per_request), "n_dispatches": met.n_dispatches,
            "window_start": clock.started_at, "window_end": end,
            "host_time": ex.device_time_stats()["host_time"],
            "device_blocked": ex.device_time_stats()["device_time"],
            "dispatch_log": getattr(fns, "log", None),
            "sched_s": res["policy"].seconds if trace else None,
            "sched_calls": res["policy"].calls if trace else None,
        }
        del svc, ex, res, met
        gc.collect()
        return out


def end_to_end(out: dict, reqs: list, seconds: float) -> dict:
    """The prefill cells' end-to-end numbers from one window's records."""
    recs = out["records"]
    met = [r for r in recs if not r["missed"] and not r["rejected"]]
    lat = sorted(r["latency"] for r in met)
    vals = {"goodput": len(met) / seconds,
            "depth_mean": (float(np.mean([r["depth"] for r in met]))
                           if met else None),
            "latency_p95": (1e3 * float(np.percentile(lat, 95))
                            if lat else None)}
    return {"values": vals, "attempted": len(reqs),
            "failed": len(reqs) - len(met), "served": met}


def check(ref_mod, m: dict, params, served: list, reqs: list, n: int,
          seed: int, *, rounding=None, block: int = 8) -> dict:
    """Compare a seeded sample of the served answers (with the longest
    prompt in it) against the plain reference at each answer's depth.

    Returns ``logit_gap`` (widest gap by which a served prediction's
    reference logit lies below the reference's best) and ``conf_rel_err``
    (widest relative error of a served confidence).  With ``rounding`` the control arithmetic
    takes the program's place: its own top prediction and confidence are
    scored against the float32 reference instead."""
    by_index = {r["index"]: r for r in reqs}
    rng = traffic_mod.seed_rng(seed, "check")
    longest = max(served, key=lambda r: (by_index[r["sample"]]["seq_len"],
                                         r["depth"], -r["sample"]))
    rest = [r for r in served if r is not longest]
    k = min(len(rest), max(0, n - 1))
    pick = [longest] + [rest[i] for i in rng.choice(len(rest), size=k,
                                                    replace=False)]
    ref = ref_mod.Reference(m, params)
    ctl = ref_mod.Reference(m, params, rounding) if rounding else None
    gaps, cerr = [], []
    groups = {}
    for r in pick:
        groups.setdefault(by_index[r["sample"]]["bucket"], []).append(r)
    for _b, rows in sorted(groups.items()):
        for i in range(0, len(rows), block):
            part = rows[i:i + block]
            x = np.stack([by_index[r["sample"]]["inputs"] for r in part])
            x = np.concatenate([x, np.repeat(x[-1:], block - len(part), 0)])
            depths = np.array([r["depth"] for r in part])
            hs = dict(ref.hidden_by_stage(x))
            hc = dict(ctl.hidden_by_stage(x)) if ctl else None
            for s in sorted(set(depths - 1)):
                sel = np.nonzero(depths - 1 == s)[0]
                lg = np.asarray(ref.exit_logits(ref_mod.exit_rows(hs[s], m),
                                                int(s)))[sel]
                if ctl is None:
                    pred = np.array([part[j]["prediction"] for j in sel])
                    conf = np.array([part[j]["conf"] for j in sel])
                else:
                    cl = np.asarray(ctl.exit_logits(
                        ref_mod.exit_rows(hc[s], m), int(s)))[sel]
                    pred, conf = cl.argmax(-1), ref_mod.confidence(cl)
                gaps += list(ref_mod.logit_gap(lg, pred))
                cerr += list(np.abs(conf / ref_mod.confidence(lg) - 1.0))
            del hs, hc
    return {"logit_gap": float(np.max(gaps)),
            "conf_rel_err": float(np.max(cerr)), "compared": len(pick)}
