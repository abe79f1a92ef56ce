"""Tuned tables: the program's own tune, for the serve cells' set-up and,
table after table, as the tune cell's window.

Every table is ``launch.tune.tune_model`` at a traffic file's shapes with
the ``tune`` settings it holds (``backend``, ``kinds``, ``eval_budget``,
``max_contractions``, ``budget_s``).  The eval budget must end every
table: the wall budget is set so high that no contraction can reach its
share of it inside a run, and :func:`tune_table` fails a table that took
longer than the smallest contraction's share.

In the tune cell each table starts from an empty registry and an empty
kernel store; between tables ``jax.clear_caches()`` drops every compiled
program, and the process runs with JAX's persistent compilation cache
off, so each table costs what a first tune on a new machine costs.  A
table is the unit of work and is never cut: the first starts when the
window opens, and another only while the tables so far say it can finish
inside the window, so a window shorter than one table times exactly one.
"""
from __future__ import annotations

import os
import shutil
import time
from typing import Any, Callable, Dict, List, Tuple

import jax

Span = Callable[[str], Any]
SHAPES = ("batch", "prompt_len", "max_len")


def least_wall_budget(model_cfg, traffic: dict) -> float:
    """The wall budget ``tune_model`` gives the smallest contraction it
    keeps: the total split by executed-FLOP share, from a harvest at the
    traffic's shapes (a lowering, no compile)."""
    from repro.launch.tune import harvest_model

    t = traffic["tune"]
    records = harvest_model(model_cfg, kinds=t["kinds"],
                            **{k: traffic[k] for k in SHAPES})
    kept = records[: t["max_contractions"]]
    total = sum(r["flop_share"] for r in kept)
    return t["budget_s"] * min(r["flop_share"] for r in kept) / total


def tune_table(model_cfg, traffic: dict, registry_path: str,
               kernel_cache: str, least_wall_s: float
               ) -> Tuple[float, Dict[str, Any]]:
    """One table, flushed to ``registry_path``; (seconds, report).  Fails
    when the table took as long as the smallest contraction's wall
    budget: the wall budget, not the eval budget, may then have ended
    it."""
    from repro.launch.tune import tune_model

    t = traffic["tune"]
    start = time.perf_counter()
    report = tune_model(model_cfg, smoke=False, backend=t["backend"],
                        registry_path=registry_path,
                        kernel_cache=kernel_cache,
                        max_contractions=t["max_contractions"],
                        budget_s=t["budget_s"], eval_budget=t["eval_budget"],
                        kinds=tuple(t["kinds"]),
                        **{k: traffic[k] for k in SHAPES})
    took = time.perf_counter() - start
    if took >= least_wall_s:
        raise RuntimeError(
            f"a table took {took:.1f} s, past the smallest contraction's "
            f"wall budget {least_wall_s:.1f} s: the wall budget, not the "
            "eval budget, may have ended it")
    return took, report


class TuneLoop:
    def __init__(self, model_cfg, traffic: dict, state_dir: str,
                 log: Callable[..., None]):
        self.model_cfg, self.traffic = model_cfg, traffic
        self.dir = os.path.join(state_dir, "tables")
        self.log = log
        self.last_registry = None
        self.least_wall_s = None

    def warm_up(self) -> None:
        """Harvest once, untimed: the same lowering every table starts
        with, and the flop shares that bound the smallest contraction's
        wall budget."""
        self.least_wall_s = least_wall_budget(self.model_cfg, self.traffic)
        self.log("tune.harvest",
                 least_contraction_wall_budget_s=self.least_wall_s)
        jax.clear_caches()

    def run(self, seconds: float, span: Span) -> Dict[str, Any]:
        tables: List[float] = []
        t0 = time.perf_counter()
        while True:
            elapsed = time.perf_counter() - t0
            if tables and elapsed + sum(tables) / len(tables) > seconds:
                break
            if not tables and elapsed >= seconds:
                break
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir)
            path = os.path.join(self.dir, "registry.json")
            with span("tune"):
                took, report = tune_table(
                    self.model_cfg, self.traffic, path,
                    os.path.join(self.dir, "kernels"), self.least_wall_s)
            tables.append(took)
            self.log("tune.table", seconds=took, n_tuned=report["n_tuned"],
                     n_harvested=report["n_harvested"],
                     flop_share_covered=report["flop_share_covered"],
                     compile={k: v for k, v in (report["compile"] or {}).items()
                              if k != "store"})
            self.last_registry = path
            with span("clear_caches"):
                jax.clear_caches()
        return {"tables": tables, "elapsed_s": time.perf_counter() - t0}

    @staticmethod
    def end_to_end(res: Dict[str, Any]) -> Dict[str, float]:
        return {"tune_s": sum(res["tables"]) / len(res["tables"])}
