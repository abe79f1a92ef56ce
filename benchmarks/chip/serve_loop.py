"""Closed-loop serving of the program's tuned prefill and decode steps.

The steps are the ones ``launch/serve`` jits, built by
``models.steps.make_prefill_step`` / ``make_decode_step`` with the tuned
registry, and driven here as ``serve_once`` drives them: ``batch`` client
slots admitted in waves (the decode step takes one scalar cache length),
one prefill per wave, then one decode step per token, and the host reads
every token as a streaming server must.  A client sends its next request
as soon as its last one finished.  Unlike ``serve_once``, the weights and
the compiled steps are made once, in set-up, so the window times only
serving.  The configuration's block module makes the weights and counts
each step's operations.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

Span = Callable[[str], Any]
#: the warm-up's prompts come from a wave index no window reaches
WARM_WAVE = 2 ** 31 - 1


def percentile(values: List[float], q: float) -> float:
    """The ``q``-th percentile, interpolated between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), q))


class ServeLoop:
    """One cell's served model: weights, compiled steps and the window."""

    def __init__(self, cfg: dict, model_cfg, traffic: dict, seed: int,
                 registry, block):
        from repro.models import steps as S

        self.cfg, self.seed, self.block = cfg, seed, block
        self.key = W.base_key(seed)
        b, p = traffic["batch"], traffic["prompt_len"]
        self.batch, self.prompt_len = b, p
        self.gen_len, self.max_len = traffic["gen_len"], traffic["max_len"]
        if p + self.gen_len > self.max_len:
            raise ValueError(f"prompt {p} + generated {self.gen_len} exceed "
                             f"max_len {self.max_len}")
        self.embeds = cfg["frontend"] == "embeds"
        self.prefill = jax.jit(S.make_prefill_step(
            model_cfg, max_len=self.max_len, registry=registry))
        self.decode = jax.jit(S.make_decode_step(model_cfg, registry=registry),
                              donate_argnums=(2,))
        self.first_token = jax.jit(
            lambda logits: jnp.argmax(logits, -1).astype(jnp.int32))
        self.params = None
        self.codes = None

    # -- set-up ---------------------------------------------------------------

    def make_weights(self) -> None:
        self.params = self.block.program_params(self.cfg, self.seed)
        if self.embeds:
            self.codes = W.code_table(self.cfg, self.key)
        jax.block_until_ready((self.params, self.codes))

    def _inputs(self, prompts):
        return {"embeds": prompts} if self.embeds else {"tokens": prompts}

    def _next(self, tok):
        if self.embeds:
            return {"embeds": _rows(self.codes, tok)}
        return {"tokens": tok[:, None]}

    def warm_up(self) -> None:
        """Compile (or load) every program the window runs: one wave's
        prompts, prefill, first token, next input and decode step."""
        prompts = W.prompts(self.cfg, self.key, WARM_WAVE, self.batch,
                            self.prompt_len)
        last, caches, cache_len = self.prefill(self.params,
                                               self._inputs(prompts))
        tok = self.first_token(last)
        nxt, _, caches = self.decode(self.params, self._next(tok), caches,
                                     np.int32(self.prompt_len))
        np.asarray(nxt)
        del caches

    # -- the window -------------------------------------------------------------

    def run(self, seconds: float, span: Span) -> Dict[str, Any]:
        """Serve waves for ``seconds``.  Tokens read after the window count
        in no metric; they are served only when no request has finished by
        then, to finish the wave in flight for the check."""
        b, p, g = self.batch, self.prompt_len, self.gen_len
        steps = {"prefill": {"m": b * p, "count": 0, "flops": 0.0},
                 "decode": {"m": b, "count": 0, "flops": 0.0}}
        ttft, itl = [], []
        finished: List[Dict[str, Any]] = []
        emitted = admitted = 0
        t0 = time.perf_counter()
        t_end = t0 + seconds
        sent = t_last = t0
        wave = 0
        late = False
        while not late and time.perf_counter() < t_end:
            with span("admit"):
                prompts = W.prompts(self.cfg, self.key, wave, b, p)
                admitted += b
            with span("prefill"):
                last, caches, _ = self.prefill(self.params,
                                               self._inputs(prompts))
                tok = self.first_token(last)
            steps["prefill"]["count"] += 1
            steps["prefill"]["flops"] += self.block.model_flops(self.cfg, b,
                                                                p, p)
            with span("read_token"):
                served = [np.asarray(tok)]
            t_last = time.perf_counter()
            ttft.extend([t_last - sent] * b)
            emitted += b
            for i in range(1, g):
                if not late and time.perf_counter() >= t_end:
                    if finished:
                        break
                    late = True
                with span("decode"):
                    tok, _, caches = self.decode(
                        self.params, self._next(tok), caches,
                        np.int32(p + i - 1))
                with span("read_token"):
                    served.append(np.asarray(tok))
                if late:
                    continue
                t = time.perf_counter()
                itl.extend([t - t_last] * b)
                t_last = t
                emitted += b
                steps["decode"]["count"] += 1
                steps["decode"]["flops"] += self.block.model_flops(
                    self.cfg, b, 1, p + i)
            del caches
            if len(served) == g:
                finished.append({"wave": wave,
                                 "tokens": np.stack(served, axis=1)})
            sent = t_last
            wave += 1
        return {"elapsed_s": t_last - t0, "emitted": emitted,
                "admitted": admitted, "waves": wave, "ttft": ttft,
                "itl": itl, "finished": finished, "steps": steps}

    def end_to_end(self, res: Dict[str, Any]) -> Dict[str, float]:
        out = {"output_tokens_per_s": res["emitted"] / res["elapsed_s"]}
        if res["itl"]:
            out["itl_p95_ms"] = percentile(res["itl"], 95) * 1e3
        if res["ttft"]:
            out["ttft_mean_ms"] = float(np.mean(res["ttft"])) * 1e3
        return out

    def free(self) -> None:
        self.params = self.codes = None
        self.prefill = self.decode = self.first_token = None

    # -- what the reference needs ---------------------------------------------------

    def reference_inputs(self, wave: int, slots: List[int],
                         tokens: np.ndarray):
        """The sequences whose logits predicted the served tokens: the
        prompt, then the inputs made from every served token but the
        last.  Returns (inputs, positions of the served tokens)."""
        p = self.prompt_len
        prompts = W.prompts(self.cfg, self.key, wave, self.batch, p)
        prompts = np.asarray(prompts[np.asarray(slots)])
        prev = tokens[:, :-1]
        if self.embeds:
            codes = np.asarray(W.code_table(self.cfg, self.key)
                               .astype(jnp.float32))
            seq = np.concatenate([prompts.astype(np.float32), codes[prev]],
                                 axis=1)
        else:
            seq = np.concatenate([prompts, prev], axis=1)
        return seq, list(range(p - 1, p - 1 + tokens.shape[1]))


@jax.jit
def _rows(table, tok):
    return jnp.take(table, tok, axis=0)[:, None]
