"""CPU tests of the yardstick itself: trace reduction, work counts, latency
arithmetic, traffic, peaks, file discovery, configurations, weights and
the reference."""
import json
import os

import numpy as np
import pytest

from benchtools import BENCH, TINY_MODEL

import generator
import peaks
import registry
import xplane
import work
from driver import Event, RunRecord, Served, quantile

CONFIGS = ["glm4-9b-chat.l8", "qwen1.5-4b.l16"]


# ----------------------------------------------------------------- trace
def _toy_trace():
    ms = 1_000_000
    host = [  # (annotation, start, end, event index)
        ("bench.window", 0, 100 * ms, None),
        ("engine.prefill_done", 0, 40 * ms, 0),
        ("engine.decode_done", 40 * ms, 60 * ms, 1),
        ("engine.arrival", 60 * ms, 61 * ms, 2),
        ("engine.decode_done", 70 * ms, 95 * ms, 3),
    ]
    pk = ('%closed_call.9 = f32[4096,4096] custom-call(s32[2] %a), '
          'custom_call_target="tpu_custom_call"')
    dk = ('%tpu_custom_call.1 = (f32[2,1,32,128]) custom-call(s32[2] %b), '
          'custom_call_target="tpu_custom_call"')
    other = '%custom-call.2 = bf16[8] custom-call(), custom_call_target="AllocateBuffer"'
    dev = {"/device:TPU:0": [
        ("fusion.1", 2 * ms, 10 * ms),
        (pk, 10 * ms, 30 * ms),                  # prefill span
        ("fusion.2", 25 * ms, 35 * ms),          # overlaps the kernel
        (dk, 45 * ms, 50 * ms),                  # decode span i=1
        (other, 50 * ms, 52 * ms),               # not a kernel
        (dk, 75 * ms, 78 * ms),                  # decode span i=3
        ("fusion.1", 90 * ms, 120 * ms),         # runs past the window
    ]}
    return host, dev


def test_trace_busy_idle_and_kernels():
    host, dev = _toy_trace()
    pk, dk = dev["/device:TPU:0"][1][0], dev["/device:TPU:0"][3][0]
    out = xplane.reduce(host, dev)
    # busy: [2,35] + [45,52] + [75,78] + [90,100] = 33 + 7 + 3 + 10 ms
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.053)
    assert out["kernel_s"] == pytest.approx({0: 0.020, 1: 0.005, 3: 0.003})
    assert out["kernels_outside_spans"] == 0
    assert out["kernel_names"] == {"%closed_call.9[pallas]": 1,
                                   "%tpu_custom_call.1[pallas]": 2}
    ops = dict((n, t) for n, t in out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.018)  # clipped at the close
    # idle, named by the span open at its midpoint: [52,75] (no span at
    # 63.5), [78,90] (decode i=3), [35,45] (decode i=1 from 40), [0,2]
    assert out["idle_gaps"] == [
        ["driver.wait", pytest.approx(0.023)],
        ["engine.decode_done", pytest.approx(0.012)],
        ["engine.decode_done", pytest.approx(0.010)],
        ["engine.prefill_done", pytest.approx(0.002)],
    ]


def test_trace_without_window_or_device_reads_nothing():
    host, dev = _toy_trace()
    assert xplane.reduce(host[1:], dev) is None
    assert xplane.reduce(host, {}) is None


def test_ops_are_named_by_their_program():
    host, dev = _toy_trace()
    ms = 1_000_000
    ops = [op + ("jit_step(123)" if i == 1 else "",)
           for i, op in enumerate(dev["/device:TPU:0"])]
    out = xplane.reduce(host, {"/device:TPU:0": ops})
    assert "jit_step:%closed_call.9[pallas]" in dict(out["device_ops"])
    assert out["kernel_s"][0] == pytest.approx(20 * ms / 1e9)


def test_union_length_handles_nesting_and_order():
    assert xplane.union_length([(5, 9), (0, 3), (1, 2), (8, 12)]) == 10


# ------------------------------------------------------------------ work
@pytest.mark.parametrize("name, per_layer", [
    # q, k, v, o projections and three SwiGLU matrices, by hand
    ("glm4-9b-chat.l8", 4096 * 4096 + 2 * 4096 * 256 + 4096 * 4096
     + 3 * 4096 * 13696),
    ("qwen1.5-4b.l16", 4 * 2560 * 2560 + 3 * 2560 * 6912),
])
def test_work_matches_hand_counts(name, per_layer):
    m = registry.config(name)["model"]
    assert work.matmul_params(m) == m["n_layers"] * per_layer
    h, kvh, d, layers = m["n_heads"], m["n_kv_heads"], m["d_head"], m["n_layers"]
    # a 4-token prompt attends 1+2+3+4 = 10 pairs
    assert work.attn_pairs(0, 4) == 10
    assert work.attn_pairs(3, 2) == 2 * 3 + 3
    flops = work.step_flops(m, [(0, 4)], 1)
    assert flops == (2 * layers * per_layer * 4 + 4 * h * d * 10 * layers
                     + 2 * m["d_model"] * m["vocab_size"])
    f, b = work.decode_attn(m, 1000)
    assert f == 4 * h * d * 1000 * layers
    assert b == (2 * 1000 * kvh * d + 2 * h * d) * 2 * layers
    f, b = work.prefill_attn(m, 0, 4)
    assert f == 4 * h * d * 10 * layers
    assert b == (2 * 4 * h * d + 2 * 4 * kvh * d) * 2 * layers
    pk = peaks.peaks("TPU v5 lite")
    # decode at long context is bound by HBM, a long prefill by the MXU
    assert work.least_time(*work.decode_attn(m, 10_000), pk) == pytest.approx(
        work.decode_attn(m, 10_000)[1] / pk["hbm_bytes_per_s"])
    assert work.least_time(*work.prefill_attn(m, 0, 8192), pk) == pytest.approx(
        work.prefill_attn(m, 0, 8192)[0] / pk["bf16_flops"])


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


# ------------------------------------------------------- ttft and tbt
def _record():
    reqs = [
        Served(due=0.0, n_prompt=10, max_new=3, stamps=[0.5, 0.7, 1.0]),
        Served(due=1.0, n_prompt=10, max_new=3, stamps=[3.0, 3.5]),
        Served(due=2.0, n_prompt=10, max_new=3, stamps=[]),        # censored
        Served(due=3.0, n_prompt=10, max_new=3, stamps=[4.5]),     # late
        Served(due=3.5, n_prompt=10, max_new=3, refused=True),
    ]
    carried = [Served(due=-2.0, n_prompt=5, max_new=9,
                      stamps=[-1.0, -0.5, 0.25, 2.0])]
    return RunRecord(4.0, {}, {}, reqs, carried, [])


def test_ttft_counts_censored_and_refused_requests():
    rec = _record()
    # 0.5, 2.0, censored 4-2=2.0, stamped after the close 4-3=1.0, refused 0.5
    assert sorted(rec.ttfts()) == pytest.approx([0.5, 0.5, 1.0, 2.0, 2.0])
    assert quantile(rec.ttfts(), 0.5) == pytest.approx(1.0)


def test_tbt_pools_gaps_ending_inside_the_window():
    rec = _record()
    # 0.2, 0.3, 0.5 and the carried request's 0.75 and 1.75 (its gap ending
    # at -0.5 ended before the window)
    assert sorted(rec.gaps()) == pytest.approx([0.2, 0.3, 0.5, 0.75, 1.75])
    assert rec.tokens_in_window() == 3 + 2 + 0 + 2


def test_end_to_end_readers_on_a_synthetic_timeline():
    rec = _record()
    rec.setup_s = 12.5
    read = {n: registry.metric(n)(rec) for n in
            ("ttft_p50_s", "ttft_p90_s", "tbt_p95_ms", "out_tok_s", "setup_s",
             "prompt_tok_s")}
    assert read["out_tok_s"] == pytest.approx(7 / 4.0)
    # answered inside the window: the first two requests; the carried one
    # and the one stamped after the close are not
    assert read["prompt_tok_s"] == pytest.approx(20 / 4.0)
    assert read["tbt_p95_ms"] == pytest.approx(
        1e3 * float(np.quantile([0.2, 0.3, 0.5, 0.75, 1.75], 0.95)))
    assert read["ttft_p90_s"] == pytest.approx(2.0)
    assert read["setup_s"] == 12.5


def test_per_layer_readers_return_nothing_without_their_source():
    rec = _record()
    for name in ("prefill_attn_roofline", "paged_decode_roofline",
                 "device_idle", "decode_batch", "decode_iter_ms",
                 "prefill_tok_s", "prefill_mfu", "decode_mfu",
                 "kv_upload_slots"):
        assert registry.metric(name)(rec) is None, name


def test_per_layer_readers_on_synthetic_events():
    m = registry.config("qwen1.5-4b.l16")["model"]
    pk = peaks.peaks("TPU v5 lite")
    evs = [Event("prefill_done", 0.0, 0.5, prefill=[(0, 1000)], sampled=1),
           Event("decode_done", 0.5, 0.6, decode=[1000, 2000], sampled=2,
                 uploads=2),
           Event("decode_done", 0.6, 0.8, decode=[1001], sampled=1,
                 uploads=1)]
    rec = RunRecord(1.0, m, pk, [], [], evs)
    rec.trace = {"busy_s": 0.25, "window_s": 1.0,
                 "kernel_s": {0: 0.01, 1: 0.002, 2: 0.001}}
    get = lambda n: registry.metric(n)(rec)  # noqa: E731
    assert get("decode_batch") == pytest.approx(1.5)
    assert get("decode_iter_ms") == pytest.approx(150.0)
    assert get("kv_upload_slots") == pytest.approx(1.5)
    assert get("prefill_tok_s") == pytest.approx(2000.0)
    assert get("device_idle") == pytest.approx(75.0)
    want = 100 * work.step_flops(m, [(0, 1000)], 1) / (0.5 * pk["bf16_flops"])
    assert get("prefill_mfu") == pytest.approx(want)
    lt = work.least_time(*work.prefill_attn(m, 0, 1000), pk)
    assert get("prefill_attn_roofline") == pytest.approx(100 * lt / 0.01)
    f1 = [work.decode_attn(m, c) for c in (1000, 2000)]
    lt1 = work.least_time(sum(f for f, _ in f1), sum(b for _, b in f1), pk)
    lt2 = work.least_time(*work.decode_attn(m, 1001), pk)
    assert get("paged_decode_roofline") == pytest.approx(
        100 * (lt1 + lt2) / 0.003)


# --------------------------------------------------------------- traffic
def test_every_seed_gets_the_same_work_with_its_own_tokens():
    mix = registry.traffic("leval32k")
    a = generator.plan(mix, 0.5, 50.0, 1, 1000, stream=1)
    b = generator.plan(mix, 0.5, 50.0, 2**40 + 3, 1000, stream=1)
    assert len(a) == len(b) == 25
    lens = lambda p: sorted(len(x.prompt) for x in p)  # noqa: E731
    outs = lambda p: sorted(x.max_new for x in p)  # noqa: E731
    gaps = lambda p: sorted(np.diff([0.0] + [x.due_s for x in p]))  # noqa: E731
    assert lens(a) == lens(b) and outs(a) == outs(b)
    assert gaps(a) == pytest.approx(gaps(b))
    # the seed draws the order: which prompt comes when
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert [p.due_s for p in a] != pytest.approx([p.due_s for p in b])
    assert not all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert all(0 < p.due_s < 50.0 for p in a + b)
    assert all(2700 <= len(p.prompt) <= 32768 and 16 <= p.max_new <= 128
               for p in a)
    # the window's stream is not the warm-up's
    w = generator.plan(mix, 0.5, 50.0, 1, 1000, stream=0)
    assert [len(p.prompt) for p in w] != [len(p.prompt) for p in a]
    again = generator.plan(mix, 0.5, 50.0, 1, 1000, stream=1)
    assert all((x.prompt == y.prompt).all() and x.due_s == y.due_s
               for x, y in zip(a, again))


def test_lengths_follow_the_mix_quantiles():
    mix = registry.traffic("sharegpt")
    lens = sorted(len(p.prompt) for p in generator.plan(
        mix, 10.0, 100.0, 5, 100, stream=0))
    assert lens[len(lens) // 2] == pytest.approx(320, rel=0.02)
    assert lens[0] >= 4 and lens[-1] == 2300


# ----------------------------------------------------- found by their name
def test_new_cell_mix_and_metric_are_found_by_their_files(tmp_path):
    root = str(tmp_path)
    for sub in ("workloads", "traffic", "metrics", "configs"):
        os.makedirs(os.path.join(root, sub))
    json.dump({"prompt_median": 100, "prompt_sigma": 0.5, "prompt_min": 10,
               "prompt_max": 400, "out_min": 1, "out_max": 9},
              open(os.path.join(root, "traffic", "bursty.json"), "w"))
    json.dump({"config": "c", "traffic": "bursty", "rate": 2.0,
               "warmup_s": 1, "sample_tokens": 8,
               "sample_requests": 2,
               "limits": {"widest_logit_error": 0.1}},
              open(os.path.join(root, "workloads", "c.bursty.json"), "w"))
    json.dump({"model": TINY_MODEL},
              open(os.path.join(root, "configs", "c.json"), "w"))
    with open(os.path.join(root, "metrics", "mean_gap_s.py"), "w") as f:
        f.write("def value(rec):\n    g = rec.gaps()\n"
                "    return sum(g) / len(g) if g else None\n")
    cell = registry.cell("c.bursty", root)
    assert registry.traffic(cell["traffic"], root).prompt_max == 400
    assert registry.config(cell["config"], root)["model"]["d_model"] == 64
    assert registry.metric("mean_gap_s", root)(_record()) == pytest.approx(
        3.5 / 5)
    with pytest.raises(FileNotFoundError):
        registry.cell("absent", root)


def test_benchmark_names_a_file_for_every_cell_and_metric():
    spec = json.load(open(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json")))
    for w in spec["workloads"]:
        cell = registry.cell(w["name"])
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(os.path.dirname(BENCH), c["file"]))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(registry.metric(m["name"]))


# --------------------------------------------------------- configurations
@pytest.mark.parametrize("name", CONFIGS)
def test_config_is_the_published_one_but_for_reduced_keys(name):
    c = registry.config(name)
    spec = json.load(open(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json")))
    entry = next((e for e in spec["configs"] if e["name"] == name), None)
    if entry is not None:  # a configuration no cell uses yet is not listed
        assert sorted(entry["reduced"]) == sorted(c["reduced"])
    run = dict(c["published"], **c["reduced"])
    for field, key in c["from_published"].items():
        assert c["model"][field] == run[key], (field, key)
    for key in c["reduced"]:
        assert c["reduced"][key] != c["published"][key]


def test_glm4_rope_base_and_share():
    c = registry.config("glm4-9b-chat.l8")
    assert c["model"]["rope_theta"] == 10000 * c["published"]["rope_ratio"]
    assert c["model"]["rope_fraction"] == 0.5


@pytest.mark.parametrize("name", CONFIGS + ["tiny"])
def test_weight_tree_is_the_programs(name):
    import jax

    from repro.configs.base import ModelConfig
    from repro.models import build_model

    import weights

    m = TINY_MODEL if name == "tiny" else registry.config(name)["model"]
    want = jax.eval_shape(build_model(ModelConfig(**m)).init,
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.make(m, 3))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_weights_repeat_for_a_seed_past_32_bits():
    import jax

    import weights

    m = dict(TINY_MODEL, n_layers=1)
    a = weights.make(m, 2**33 + 1)
    b = weights.make(m, 2**33 + 1)
    c = weights.make(m, 1)
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all((x == y).all() for x, y in zip(la, lb))
    assert not (la[0] == lc[0]).all()


# --------------------------------------------------------------- reference
def test_reference_agrees_with_the_programs_float32_forward():
    """At float32 with highest precision the program's plain forward and
    the reference are the same equations: logits agree to rounding."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ModelConfig
    from repro.models import build_model

    import check
    import weights

    m = dict(TINY_MODEL, dtype="float32")
    params = weights.make(m, 11)
    ref = check.reference("dense", BENCH)
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], 37)
    rows = np.arange(20, 37)
    with jax.default_matmul_precision("highest"):
        want = build_model(ModelConfig(**m)).forward(
            params, {"tokens": jnp.asarray(toks)[None]})[0][0]
        got = ref.logits(params, m, toks, rows)
    np.testing.assert_allclose(got, np.asarray(want)[rows], atol=2e-5,
                               rtol=2e-5)


def test_reference_pad_lengths():
    from references import dense

    assert [dense.pad_len(n) for n in (1, 512, 513, 768, 769, 32768)] == [
        512, 512, 768, 768, 1024, 32768]


# ------------------------------------------------------------- set-up
def test_prefill_batches_follow_the_tipping_point():
    """Every prompt alone, and the larger sets the scheduler's tipping
    point admits: here a batch fits while its tokens sum to at most 10,
    and at most three prompts go in one batch."""
    from types import SimpleNamespace

    import run

    sib = SimpleNamespace(prefill_time=lambda d, lens: sum(lens) / d,
                          prefill_tipping_point=lambda d: 10 / d)
    eng = SimpleNamespace(sib=sib, n=2, manager=SimpleNamespace(
        mcfg=SimpleNamespace(max_prefill_batch=3)))
    got = sorted(sorted(b) for b in run.prefill_batches(eng, [6, 2, 3, 12, 2]))
    assert got == sorted([[2], [3], [6], [12], [2, 2], [2, 3], [2, 6],
                          [2, 2, 3], [2, 2, 6], [3, 6]])


def test_decode_is_warmed_to_every_request_the_traffic_sends():
    import run

    one = generator.Mix("one", 100, 1.0, 10, 400, 1, 1)
    chat = generator.Mix("chat", 100, 1.0, 10, 400, 2, 9)
    a = generator.plan(chat, 2.0, 5.0, 3, 100, stream=0)
    b = generator.plan(chat, 2.0, 10.0, 3, 100, stream=1)
    assert run.decode_reach(one, a, b) == 0
    assert run.decode_reach(chat, a, b) == len(a) + len(b) == 30
