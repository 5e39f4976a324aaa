"""Path driver ``token_decode``: per-token anytime decode through ``Service``.

The window runs the program's decode launcher path: the ``conf-target``
policy, the ``decode`` executor (one jitted ``decode_step`` per depth, each
depth recomputing from the embedding; ``speculate`` dispatches the next
depth before the current confidence is read) and the ``token-loop``
source, which issues token t+1 when token t retires.  A batch of rows
decodes together from one seeded start token each, greedily, until the
window's seconds are up or the cache slots are full.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import instrument, traffic as traffic_mod


class DecodeCell:
    def __init__(self, m: dict, tr: dict, cfg):
        self.m, self.tr, self.cfg = m, tr, cfg
        self.params = None
        self.steps = None

    @property
    def batch(self) -> int:
        return int(self.tr["batch"])

    @property
    def slots(self) -> int:
        return int(self.tr["cache_slots"])

    def setup(self, params) -> None:
        """Jit one step per depth and run each once (compile or cache load)."""
        import jax
        import jax.numpy as jnp
        from repro.models import decode_step, init_decode_cache
        self.params = params
        cfg = self.cfg
        n = len(cfg.stage_boundaries())
        self.steps = [jax.jit(lambda p, c, t, pos, _d=d: decode_step(
            cfg, p, c, t, pos, upto_stage=_d)) for d in range(1, n + 1)]
        cache = init_decode_cache(cfg, self.batch, self.slots)
        tok = jnp.zeros((self.batch,), jnp.int32)
        pos = jnp.zeros((self.batch,), jnp.int32)
        for step in self.steps:
            jax.block_until_ready(step(params, cache, tok, pos)[0].logits[-1])
        del cache
        # the host-side ops of the loop (positions, argmax, confidence
        # readback) compile on first use: two tokens through the same path
        self.serve(float("inf"), 0, trace=False, max_tokens=2)

    def start_tokens(self, seed: int) -> np.ndarray:
        rng = traffic_mod.seed_rng(seed, "start-tokens")
        return rng.integers(1, self.m["vocab_size"], size=self.batch)

    def serve(self, seconds: float, seed: int, *, trace: bool,
              max_tokens: int = None) -> dict:
        import jax.numpy as jnp
        from repro.launch.serve import DecodeExecutor, TokenLoopSource
        from repro.models import init_decode_cache
        from repro.serving import ServeSpec, Service
        from repro.serving.runtime.clock import WallClock

        n_stages = len(self.steps)
        n_tokens = max_tokens or self.slots - 1
        pol = self.tr["policy"]
        spec = ServeSpec(
            policy=pol["name"], policy_args=pol.get("args", {}),
            executor="decode",
            executor_args={"speculate": bool(self.tr["speculate"])},
            clock="wall", source="token-loop",
            source_args={"n_tokens": n_tokens, "n_stages": n_stages},
            batching={"mode": "none", "stage_times": [0.0] * n_stages})
        spec.validate()
        clock = instrument.window_clock(WallClock, annotate_sleep=trace)
        tok0 = jnp.asarray(self.start_tokens(seed), jnp.int32)
        cache = init_decode_cache(self.cfg, self.batch, self.slots)
        ex = DecodeExecutor(self.steps, self.params, cache, tok0,
                            speculate=bool(self.tr["speculate"]))
        split = {}
        for name in ("submit", "complete", "commit"):
            instrument.time_method(ex, name, split)
        if trace:
            instrument.annotate_method(ex, "submit", "perfbench.dispatch")
            instrument.annotate_method(ex, "complete", "perfbench.wait_device")
            instrument.annotate_method(ex, "commit", "perfbench.commit")
        tokens, depths = [], []

        def advance(task, out):
            depths.append(task.executed)
            nxt = jnp.argmax(out.logits[-1], -1).astype(jnp.int32)
            tokens.append(nxt)
            return nxt

        class WindowedTokenLoop(TokenLoopSource):
            """The launcher's token loop, closed once the window is up."""

            def on_retire(self, task, now):
                super().on_retire(task, now)
                if now >= seconds:
                    self._ready = False

        src = WindowedTokenLoop(n_tokens, n_stages, ex, advance)
        svc = Service.from_spec(spec, clock=clock, executor=ex, source=src,
                                steps=self.steps, params=self.params,
                                cache=cache, tok=tok0, advance=advance)
        try:
            met = svc.run()
        finally:
            end = time.perf_counter()
            clock.span.close()
        toks = np.stack([np.asarray(t) for t in tokens], axis=1) \
            if tokens else np.zeros((self.batch, 0), np.int64)
        out = {"window_start": clock.started_at, "window_end": end,
               "n_tokens": len(tokens), "depths": np.asarray(depths),
               "start": np.asarray(tok0), "tokens": toks,
               "speculated": ex.speculated, "spec_hits": ex.spec_hits,
               "mean_depth": met.mean_depth, "split_s": split}
        svc.close()
        del svc, ex, src, met, cache
        gc.collect()
        return out


def end_to_end(out: dict) -> dict:
    span = out["window_end"] - out["window_start"]
    n = out["n_tokens"]
    return {"values": {"token_time": 1e3 * span / n if n else None},
            "attempted": n, "failed": int(np.sum(out["depths"] < 1))}


def check(ref_mod, m: dict, params, out: dict, slots: int, *,
          rounding=None, chunk: int = 256) -> dict:
    """Every served token against the plain reference's full forward pass
    over the same sequence: the widest gap by which a served token's
    reference logit lies below the reference's best at its position.  With
    ``rounding`` the control's own top token is scored instead."""
    seqs = np.concatenate([out["start"][:, None], out["tokens"]], axis=1)
    B, T = seqs.shape
    x = np.zeros((B, slots), np.int32)
    x[:, :T] = seqs
    depths = out["depths"]
    ref = ref_mod.Reference(m, params)
    hs = dict(ref.hidden_by_stage(x))
    ctl = ref_mod.Reference(m, params, rounding) if rounding else None
    hc = dict(ctl.hidden_by_stage(x)) if ctl else None
    gaps = []
    for s in sorted(set(int(d) - 1 for d in depths)):
        pos = np.nonzero(depths - 1 == s)[0]          # positions t -> t+1
        for i in range(0, len(pos), chunk):
            p = pos[i:i + chunk]
            rows = hs[s][:, p].reshape(B * len(p), -1)
            lg = np.asarray(ref.exit_logits(rows, s))
            if hc is None:
                served = seqs[:, p + 1].reshape(-1)
            else:
                cl = np.asarray(ctl.exit_logits(
                    hc[s][:, p].reshape(B * len(p), -1), s))
                served = cl.argmax(-1)
            gaps += list(ref_mod.logit_gap(lg, served))
    return {"logit_gap": float(np.max(gaps)) if gaps else float("inf"),
            "compared": len(gaps)}
