"""A miniature benchmark tree for the benchmark's own tests: one tiny
dense configuration, one short chat mix and one cell, that run on the CPU
in seconds."""
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_MODEL = {
    "name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 2, "d_head": 16, "d_ff": 128,
    "vocab_size": 256, "qkv_bias": True, "rope_theta": 10000.0,
    "rope_fraction": 0.5, "norm_eps": 1e-6, "ffn_kind": "swiglu",
    "norm_kind": "rmsnorm", "max_seq_len": 128, "tie_embeddings": False,
    "dtype": "bfloat16",
}


def make_root(path, *, cell="tiny.chat", rate=40.0, limit=0.5,
              prompts=(8, 40), outs=(2, 6), warmup=0.5):
    """A benchmark tree under ``path``: the real metric readers and
    references, and tiny data files.  Returns (root, bench dir)."""
    bench = os.path.join(path, "bench")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for sub in ("metrics", "references"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub),
                        dirs_exist_ok=True)
    spec = json.load(open(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json")))
    spec["workloads"] = [{"name": cell, "config": "tiny", "traffic": "chat",
                          "chips": 1, "why": "test"}]
    for m in spec["per_layer"]:
        m["workloads"] = [cell]
    json.dump(spec, open(os.path.join(path, "BENCHMARK.json"), "w"))
    json.dump({"name": "tiny", "model": TINY_MODEL, "reference": "dense",
               "serving": {"instances": 2, "slots": 512, "page_size": 16}},
              open(os.path.join(bench, "configs", "tiny.json"), "w"))
    json.dump({"prompt_median": 16, "prompt_sigma": 1.0,
               "prompt_min": prompts[0], "prompt_max": prompts[1],
               "out_min": outs[0], "out_max": outs[1]},
              open(os.path.join(bench, "traffic", "chat.json"), "w"))
    json.dump({"config": "tiny", "traffic": "chat", "rate": rate,
               "warmup_s": warmup, "sample_tokens": 16, "sample_requests": 4,
               "limits": {"widest_logit_error": limit}},
              open(os.path.join(bench, "workloads", f"{cell}.json"), "w"))
    return str(path), bench
