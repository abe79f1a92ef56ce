"""LoopTuner — the framework-facing auto-tuning service.

This is the paper's headline property as a first-class feature: a *trained*
policy tunes a new kernel in ~a second of pure inference (§III: "the policy
network quickly reaches the desired state in a matter of seconds"), and the
resulting schedule is lowered to Pallas BlockSpecs through the registry.

    tuner = LoopTuner.from_checkpoint("apex.pkl", backend="tpu")
    entry = tuner.tune(matmul_benchmark(512, 512, 512))
    # -> registry now maps mm:512x512x512 -> {block, grid_order, gflops}

Fallback paths: ``policy="search"`` uses the best traditional search under a
budget (for machines without a trained checkpoint), ``policy="default"``
records the untuned nest.
"""
from __future__ import annotations

import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .actions import CPU_SPLITS, TPU_SPLITS, actions_from_names, build_action_space
from .backend import backend_name, make_backend
from .encoders import EncoderConfig, get_encoder, make_policy_act
from .env import LoopTuneEnv
from .loop_ir import Contraction, matmul_benchmark
from .measure import measure_settings
from .registry import ScheduleRegistry
from .rl_common import ActFn, greedy_rollout, greedy_rollout_vec, load_checkpoint
from .schedule_cache import ScheduleCache
from .search import beam_search, greedy_search
from .surrogate import SurrogateScorer
from .vec_env import VecLoopTuneEnv
from ..runtime.spans import timed

# "warn once": legacy checkpoints without a recorded peak trip this on the
# first load in a process, not on every tune() call
_WARNED_NO_PEAK = False


# legacy checkpoints (no meta) carry only the algo name; map it to the
# network head the trainer used so they keep loading with flat defaults
_DEFAULT_HEADS = {
    "dqn": "q",
    "apex_dqn": "dueling",
    "ppo": "actor_critic",
    "a2c": "actor_critic",
    "impala": "actor_critic",
}


def load_policy(path: str) -> Tuple[ActFn, Dict[str, Any], EncoderConfig]:
    """Rebuild greedy acting from a checkpoint's embedded metadata.

    Returns ``(act, meta, encoder_config)``.  The metadata (head, encoder
    config, action space — see ``encoders.checkpoint_meta``) removes all
    guessing; pre-metadata checkpoints fall back to the per-algo default
    head and the flat encoder, which is exactly what produced them."""
    import jax
    import jax.numpy as jnp

    d = load_checkpoint(path)
    algo, meta = d["algo"], d["meta"]
    head = meta.get("head") or _DEFAULT_HEADS.get(algo)
    if head is None:
        raise ValueError(f"unknown algo {algo!r} in {path}")
    enc_cfg = (EncoderConfig.from_dict(meta["encoder"])
               if meta.get("encoder") else EncoderConfig()).resolved()
    params = jax.tree.map(jnp.asarray, d["params"])
    act = make_policy_act(head, enc_cfg, meta.get("n_actions", 0))([params])
    return act, meta, enc_cfg


def make_act_from_checkpoint(path: str) -> ActFn:
    """Rebuild the greedy act() for a saved TrainResult checkpoint."""
    return load_policy(path)[0]


class LoopTuner:
    """Tunes contractions and persists schedules for the kernel layer."""

    def __init__(
        self,
        act: Optional[ActFn] = None,
        backend: str = "tpu",
        registry: Optional[ScheduleRegistry] = None,
        episode_len: int = 10,
        policy: str = "policy",  # "policy" | "search" | "default"
        search_budget_s: float = 10.0,
        featurizer=None,  # None -> env default (flat); set to match the act
        surrogate: str = "auto",  # "auto" | "off": cost-model-guided search
        cache_dir: Optional[str] = None,  # persistent compiled-kernel store
    ):
        self.act = act
        # any registered backend name ("tpu" | "numpy" | "jax" | "auto" |
        # "cpu") or a ready Backend instance — see core.backend.make_backend.
        # cache_dir (persistent fleet-wide compile cache; jax-only, others
        # tolerate it) can only be applied when the tuner builds the backend
        self.backend = (make_backend(backend, cache_dir=cache_dir)
                        if cache_dir is not None and isinstance(backend, str)
                        else make_backend(backend))
        self.cache_dir = cache_dir
        self.backend_kind = backend_name(self.backend)
        self.registry = registry if registry is not None else ScheduleRegistry()
        self.episode_len = episode_len
        self.policy = policy if act is not None or policy != "policy" else "search"
        self.search_budget_s = search_budget_s
        self.featurizer = featurizer
        if surrogate not in ("auto", "off"):
            raise ValueError(f"surrogate must be 'auto' or 'off', got {surrogate!r}")
        self.surrogate = surrogate
        splits = TPU_SPLITS if self.backend_kind == "tpu" else CPU_SPLITS
        self.actions = build_action_space(splits)
        # one evaluation cache for every env this tuner creates, so repeated
        # tune() calls and tune_many() lanes amortize each other
        self.cache = ScheduleCache()
        # reward calibration (set by from_checkpoint): when not None, every
        # env this tuner builds normalizes rewards by this peak instead of
        # re-timing the live backend's — see _calibrate / core.measure
        self.peak_override: Optional[float] = None
        self.calibration: Dict[str, Any] = {"mode": "live"}
        # one learned cost model shared by every search-mode tune() call —
        # built lazily against the first env's featurizer, then warmed by
        # each tuned benchmark's measurements (see _scorer_for)
        self._scorer: Optional[SurrogateScorer] = None
        # registry-record provenance: where did this schedule come from
        # (from_checkpoint overwrites with the checkpoint identity)
        self.provenance: Dict[str, Any] = {"policy": self.policy}

    @classmethod
    def from_checkpoint(cls, path: str, backend: Optional[str] = None,
                        **kw) -> "LoopTuner":
        """Rebuild the exact tuning setup a checkpoint was trained with: the
        network (head + encoder), the matching observation featurizer, the
        trained action space (its split ladder), and — unless overridden —
        the backend that produced the training reward signal, all from the
        embedded metadata — no defaults assumed."""
        act, meta, enc_cfg = load_policy(path)
        kw.setdefault("surrogate", meta.get("surrogate", "auto"))
        if backend is None:
            # pre-backend-metadata checkpoints were all trained on the
            # analytical model, which is also the historical default
            backend = meta.get("backend") or "tpu"
        tuner = cls(act=act, backend=backend, **kw)
        tuner.featurizer = get_encoder(enc_cfg.kind).featurizer(enc_cfg)
        if meta.get("actions") is not None:
            # the full recorded list, not just the split ladder: index i must
            # mean exactly what the policy's output unit i was trained on
            tuner.actions = actions_from_names(meta["actions"])
        tuner._calibrate(meta)
        tuner.provenance = {"policy": "policy", "checkpoint": path,
                            "algo": meta.get("algo"),
                            "trained_backend": meta.get("backend")}
        return tuner

    def _calibrate(self, meta: Dict[str, Any]) -> None:
        """Cross-backend reward calibration (see ``core.measure``).

        Rewards are normalized GFLOPS deltas, ``(g' - g) / peak``.  The
        policy's value scale is therefore tied to the ``peak`` its trainer
        recorded:

        * same executor as training — reuse the *recorded* peak, so the
          reward scale is bit-identical to training (re-timing the
          calibration kernel at load would shift every reward by the
          re-timing jitter);
        * different executor — normalize by the live executor's own peak
          (each backend's fraction-of-its-own-peak is the scale-stable
          cross-executor mapping) and surface the recorded/live ratio;
        * legacy checkpoint with no recorded peak — warn once and fall
          back to the live backend's ``peak()`` explicitly, instead of
          silently mixing scales.
        """
        global _WARNED_NO_PEAK
        recorded = meta.get("peak")
        trained_on = meta.get("backend")
        if recorded is None:
            if not _WARNED_NO_PEAK:
                _WARNED_NO_PEAK = True
                warnings.warn(
                    "checkpoint metadata records no training-time peak(); "
                    "rewards will be normalized by the live backend's peak "
                    "— the reward scale may differ from training "
                    "(re-train or re-save to embed `peak` in meta)",
                    stacklevel=3)
            self.peak_override = None
            self.calibration = {"mode": "legacy-live-peak",
                                "trained_on": trained_on}
        elif trained_on == self.backend_kind:
            self.peak_override = float(recorded)
            self.calibration = {"mode": "recorded",
                                "trained_on": trained_on,
                                "peak": float(recorded)}
        else:
            live = self.backend.peak()
            self.peak_override = None
            self.calibration = {"mode": "cross-backend",
                                "trained_on": trained_on,
                                "recorded_peak": float(recorded),
                                "live_peak": float(live),
                                "scale_ratio": float(recorded) / float(live)}

    # ------------------------------------------------------------------

    def _env_for(self, bench: Contraction) -> LoopTuneEnv:
        return LoopTuneEnv([bench], self.backend, actions=self.actions,
                           episode_len=self.episode_len, cache=self.cache,
                           featurizer=self.featurizer,
                           peak=self.peak_override)

    def _scorer_for(self, env: LoopTuneEnv) -> Optional[SurrogateScorer]:
        """The tuner-lifetime surrogate scorer (None when disabled).  Shared
        across tune() calls so the cost model learned on one contraction
        pre-ranks the next one's frontiers."""
        if self.surrogate == "off":
            return None
        if self._scorer is None:
            self._scorer = SurrogateScorer.for_env(env)
        return self._scorer

    def _record(self, kernel: str, bench: Contraction, gflops: float,
                actions: List[str], nest, dtype: str) -> Dict[str, Any]:
        """Registry write with full v2 record context: executor + hardware
        keying, the measurement spread the variance guardrails recorded for
        the winning schedule, and tuner provenance."""
        dims = tuple(bench.iter_sizes.values())
        measurement = None
        mfor = getattr(self.backend, "measurement_for", None)
        if mfor is not None and nest is not None:
            measurement = mfor(nest)
        # stamp the *measuring* host: with a remote farm the timing ran on
        # the farm's hardware, and the record key must say so — local
        # current_hardware() (registry.put's default) only when the backend
        # has no better answer (or the farm degraded to local fallback)
        mhw = getattr(self.backend, "measured_hardware", None)
        hardware = mhw() if mhw is not None else None
        mbn = getattr(self.backend, "measured_backend_name", None)
        backend = (mbn() if mbn is not None else None) or self.backend_kind
        self.registry.put(kernel, dims, gflops, list(actions), nest,
                          dtype=dtype, backend=backend,
                          hardware=hardware,
                          measurement=measurement,
                          provenance=self.provenance)
        return dict(self.registry.get(kernel, dims, dtype))

    def tune(self, bench: Contraction, kernel: str = "mm", *,
             dtype: str = "float32", budget_s: Optional[float] = None,
             max_evals: Optional[int] = None) -> Dict[str, Any]:
        """Tune one contraction; returns the registry entry.  Its
        ``looptune.contraction`` span's self time is the search's own host
        work: measuring, compiling and operands open spans of their own."""
        with timed("looptune.contraction") as sp:
            budget_s = budget_s if budget_s is not None else self.search_budget_s
            env = self._env_for(bench)
            if self.policy == "policy":
                best_g, actions, nest = greedy_rollout(env, self.act, 0)
            elif self.policy == "search":
                scorer = self._scorer_for(env)
                res = greedy_search(env, 0, lookahead=1, budget_s=budget_s,
                                    max_evals=max_evals, surrogate=scorer)
                res2 = beam_search(env, 0, width=4, order="dfs",
                                   budget_s=budget_s, max_evals=max_evals,
                                   surrogate=scorer)
                res = res2 if res2.best_gflops > res.best_gflops else res
                best_g, actions, nest = res.best_gflops, res.actions, res.best_nest
            else:  # default / untuned
                env.reset(0)
                best_g, actions, nest = env.current_gflops, [], env.nest.clone()
            # bank speculative measure-ahead work: anything the searches put in
            # flight on an async farm but never collected still lands in the
            # shared cache (a later tune() call may hit it for free)
            self.cache.drain_ahead()
            entry = self._record(kernel, bench, best_g, list(actions), nest, dtype)
        entry["tune_time_s"] = sp.seconds
        entry["base_gflops"] = env.initial_gflops
        return entry

    def tune_matmul(self, m: int, k: int, n: int) -> Dict[str, Any]:
        return self.tune(matmul_benchmark(m, k, n), kernel="mm")

    def tune_many(self, benches: Sequence[Contraction], kernel: str = "mm",
                  vec_size: int = 16, *,
                  weights: Optional[Sequence[float]] = None,
                  dtypes: Optional[Sequence[str]] = None,
                  budget_s: Optional[float] = None,
                  eval_budget: Optional[int] = None,
                  on_entry: Optional[Callable[[int, Dict[str, Any]], None]]
                  = None) -> List[Dict[str, Any]]:
        """Tune many contractions at once.

        With a trained policy, the contractions become lanes of a
        :class:`VecLoopTuneEnv` (chunks of ``vec_size``) and the policy is
        rolled out greedily over all of them simultaneously — one batched
        act() and one batched backend call per step.  Search/default
        policies fall back to per-contraction tuning.

        ``weights`` (normalized internally) split a *total* search budget —
        ``budget_s`` seconds and optionally ``eval_budget`` backend
        evaluations — across the contractions, so callers can spend the
        budget where the executed FLOPs are (see ``launch.tune``).  Without
        weights each contraction gets the tuner's per-bench default.

        ``on_entry(i, entry)`` fires as soon as contraction ``i``'s entry
        is recorded (both policy and search paths) — the hook crash-
        resumable tuning journals per-contraction progress through (see
        ``launch.tune``'s :class:`TuneJournal`).
        """
        dtypes = list(dtypes) if dtypes is not None else ["float32"] * len(benches)
        if self.policy != "policy":
            if weights is None:
                share = [None] * len(benches)
            else:
                total = float(sum(weights)) or 1.0
                share = [w / total for w in weights]
            total_s = (budget_s if budget_s is not None
                       else self.search_budget_s * len(benches))
            entries = []
            for i, (b, dt, w) in enumerate(zip(benches, dtypes, share)):
                if w is None:
                    entry = self.tune(b, kernel, dtype=dt)
                else:
                    evals = (max(2, int(round(eval_budget * w)))
                             if eval_budget is not None else None)
                    entry = self.tune(b, kernel, dtype=dt,
                                      budget_s=total_s * w, max_evals=evals)
                entries.append(entry)
                if on_entry is not None:
                    on_entry(i, entry)
            return entries
        entries: List[Dict[str, Any]] = []
        for lo in range(0, len(benches), vec_size):
            chunk = list(benches[lo:lo + vec_size])
            t0 = time.perf_counter()
            venv = VecLoopTuneEnv(chunk, self.backend, n_envs=len(chunk),
                                  actions=self.actions,
                                  episode_len=self.episode_len,
                                  cache=self.cache,
                                  featurizer=self.featurizer,
                                  peak=self.peak_override)
            best_g, names, nests = greedy_rollout_vec(
                venv, self.act, benchmark_indices=list(range(len(chunk))))
            self.cache.drain_ahead()
            per_bench_s = (time.perf_counter() - t0) / len(chunk)
            for i, bench in enumerate(chunk):
                entry = self._record(kernel, bench, float(best_g[i]),
                                     list(names[i]), nests[i],
                                     dtypes[lo + i])
                entry["tune_time_s"] = per_bench_s
                entry["base_gflops"] = float(venv.initial_gflops[i])
                entries.append(entry)
                if on_entry is not None:
                    on_entry(lo + i, entry)
        return entries

    def stats(self) -> Dict[str, Any]:
        """Observability: tuned-schedule count, the shared evaluation
        cache's hit/miss/eviction counters (how much the batched-eval
        substrate is actually amortizing), the backend's measurement
        counters (variance escalations, noisy flags, pool health) and the
        active reward calibration."""
        ms = getattr(self.backend, "measure_stats", None)
        cs = getattr(self.backend, "compile_stats", None)
        measurement = {"settings": measure_settings(self.backend),
                       **(ms() if ms is not None else {})}
        return {
            "policy": self.policy,
            "backend": self.backend_kind,
            "registry_size": len(self.registry),
            "cache": self.cache.stats(),
            # compile ledger (stable shape; zeros on compile-free backends):
            # how much wall-clock went to tracing vs. was served from the
            # in-memory/persistent kernel caches
            "compile": (cs() if cs is not None
                        else {"compile_misses": 0, "compile_hits": 0,
                              "compile_s": 0.0}),
            # stable shape regardless of whether a scorer exists yet
            "surrogate": {"mode": self.surrogate,
                          **(self._scorer.stats()
                             if self._scorer is not None else {})},
            # "measurement" is the historical name; "measure" aliases the
            # same dict so farm counters (requests/retries/reconnects/
            # degraded/farm_rtt under ["farm"]) read under either spelling
            "measurement": measurement,
            "measure": measurement,
            "calibration": dict(self.calibration),
        }

    def save(self, path: str) -> None:
        self.registry.save(path)
