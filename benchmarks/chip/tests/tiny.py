"""A throwaway root for CPU runs of the harness: a copy of the benchmark's
files, with small configurations, traffic mixes and limits added beside
them and named in a ``BENCHMARK.json`` of its own."""
import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))

TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
        "head_dim": 16, "d_ff": 128, "vocab": 512, "rope_theta": 10000.0,
        "norm_eps": 1e-6, "dtype": "bfloat16", "tie_embeddings": False,
        "reduced": ["n_layers", "d_model"]}
CONFIGS = {
    "tiny-tokens": dict(TINY, arch="phi3-mini-3.8b", act="silu",
                        frontend="tokens"),
    "tiny-embeds": dict(TINY, arch="musicgen-large", act="gelu",
                        frontend="embeds"),
    # the zoo's MoE FFN on every layer, in float32 so that no near-tie in
    # the router's top-k goes one way in the program and the other in the
    # reference; a capacity factor of n_experts / top_k gives every expert
    # room for every token, so no token drops
    "tiny-moe": dict(TINY, arch="olmoe-1b-7b", act="silu", frontend="tokens",
                     dtype="float32", block="moe_topk",
                     moe={"n_experts": 4, "top_k": 2, "d_ff_expert": 32,
                          "capacity_factor": 2.0}),
}
TRAFFIC = {
    "tiny_serve": {"kind": "serve", "loop": "closed", "batch": 4,
                   "prompt_len": 8, "gen_len": 16, "max_len": 24,
                   "tune": {"backend": "tpu", "kinds": ["decode", "prefill"],
                            "eval_budget": 4, "max_contractions": 3,
                            "budget_s": 1e9},
                   "check_requests": 4},
    "tiny_tune": {"kind": "tune", "batch": 2, "prompt_len": 8, "max_len": 16,
                  "tune": {"backend": "jax", "kinds": ["decode", "prefill"],
                           "eval_budget": 4, "max_contractions": 2,
                           "budget_s": 1e9}},
}
WORKLOADS = {"tiny-tokens.serve": ("tiny-tokens", "tiny_serve"),
             "tiny-embeds.serve": ("tiny-embeds", "tiny_serve"),
             "tiny-moe.serve": ("tiny-moe", "tiny_serve"),
             "tiny-tokens.tune": ("tiny-tokens", "tiny_tune")}
LIMITS = {"tiny-tokens.serve": {"max_logit_gap": 0.05},
          "tiny-embeds.serve": {"max_logit_gap": 0.05},
          "tiny-moe.serve": {"max_logit_gap": 0.05},
          "tiny-tokens.tune": {"kernel_rel_err": 0.01}}


def make_root(tmp: str, extra_metric: str = "") -> str:
    """Copy the benchmark under ``tmp`` and add the tiny cells to it."""
    root = os.path.join(tmp, "root")
    data = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(CHIP, data, ignore=shutil.ignore_patterns(
        "state", "__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"] = [{"name": n, "source": "tests", "reduced": c["reduced"],
                       "file": f"benchmarks/chip/configs/{n}.json",
                       "why": "CPU test"} for n, c in CONFIGS.items()]
    doc["workloads"] = [{"name": w, "config": c, "traffic": t, "chips": 1,
                         "why": "CPU test"} for w, (c, t) in WORKLOADS.items()]
    for m in doc["end_to_end"] + doc["per_layer"]:
        m.pop("workloads", None)
    serve_only = {"output_tokens_per_s", "itl_p95_ms", "ttft_mean_ms"}
    for m in doc["end_to_end"]:
        if m["name"] in serve_only:
            m["workloads"] = [w for w in WORKLOADS if w.endswith(".serve")]
        elif m["name"] == "tune_s":
            m["workloads"] = ["tiny-tokens.tune"]
    for m in doc["per_layer"]:
        m["workloads"] = ([w for w in WORKLOADS if w.endswith(".serve")]
                          if m["moves"] != "tune_s" else ["tiny-tokens.tune"])
    if extra_metric:
        doc["per_layer"].append(
            {"name": extra_metric, "unit": "steps", "better": "higher",
             "source": "program_counter", "layer": "tests",
             "moves": "output_tokens_per_s",
             "workloads": ["tiny-tokens.serve"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f, indent=1)
    for n, c in CONFIGS.items():
        _dump(os.path.join(data, "configs", n + ".json"), dict(c, name=n))
    for n, t in TRAFFIC.items():
        _dump(os.path.join(data, "traffic", n + ".json"), t)
    for n, lim in LIMITS.items():
        _dump(os.path.join(data, "limits", n + ".json"), lim)
    return root


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def break_decode(monkeypatch, fault):
    """Plant a fault in the program's decode step: ``state`` returns the
    cache unchanged, ``token`` alters the served token where the step
    produces it."""
    from repro.models import steps as S

    make = S.make_decode_step

    def broken(cfg, registry=None):
        step = make(cfg, registry=registry)

        def serve_step(params, batch, caches, cache_len):
            nxt, logits, new = step(params, batch, caches, cache_len)
            if fault == "state":
                return nxt, logits, caches
            return (nxt + 1) % cfg.vocab, logits, new
        return serve_step

    monkeypatch.setattr(S, "make_decode_step", broken)
