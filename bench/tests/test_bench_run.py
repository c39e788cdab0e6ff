"""The harness end to end on the CPU at a toy size: the open-loop driver
serving a tiny dense model through the program's engine, the metrics each
kind of run reports, and `correct` coming out false with the served path
broken underneath."""
import json
import os

import numpy as np
import pytest

import run
from benchtools import make_root

SEED = 2**33 + 7
LIMIT = 0.01  # the tiny cell's limit; sound runs read ~0.003, fp8 ~0.03
TINY = dict(rate=2.0, warmup=2.0, limit=LIMIT, prompts=(8, 24), outs=(2, 4))


@pytest.fixture(scope="module", autouse=True)
def warm(tmp_path_factory):
    """The same run once first, so that the CPU's compiles of the shapes it
    meets (the eager decode path compiles per batch size) fall before the
    runs under test; on the chip the persistent cache plays this part."""
    root, bench = make_root(tmp_path_factory.mktemp("warm"), **TINY)
    run.run_cell("tiny.chat", SEED, 4.0, False, root=root, bench_dir=bench,
                 require_chip=False)


@pytest.mark.parametrize("traced", [False, True])
def test_open_loop_run_reports_its_metrics(tmp_path, traced):
    root, bench = make_root(tmp_path, **TINY)
    result, extra = run.run_cell("tiny.chat", SEED, 4.0, traced,
                                 root=root, bench_dir=bench,
                                 require_chip=False)
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    kind = "per_layer" if traced else "end_to_end"
    want = {m["name"] for m in spec[kind]}
    got = set(result["metrics"])
    assert got <= want
    if traced:
        # the CPU has no device plane: the trace-read metrics stay silent
        assert {"queue_wait_s", "prefill_tok_s", "prefill_mfu", "ttft_p50_s",
                "ttft_p90_s"} <= got
        assert not got & {"device_idle", "prefill_attn_roofline"}
    else:
        assert got == {"prompt_tok_s", "setup_s"}
    assert result["correct"] is True
    assert result["attempted"] == 8 and result["failed"] == 0
    assert list(result)[-1] == "check"
    gap = result["check"]["widest_logit_error"]
    assert 0.0 <= gap["value"] <= gap["limit"]
    assert extra["check_served_tokens"] >= 1
    assert extra["requests"]["due"] == 8
    rec = extra["record"]
    assert rec.tokens_in_window() > 0 and rec.events


def _run(tmp_path, fault=None, **tiny):
    root, bench = make_root(tmp_path, **dict(TINY, **tiny))
    result, extra = run.run_cell("tiny.chat", SEED, 4.0, False, root=root,
                                 bench_dir=bench, require_chip=False,
                                 fault=fault)
    assert extra["check_served_tokens"] > 0  # the comparison had tokens
    return result


def test_token_altered_where_it_is_sampled_fails(tmp_path):
    def fault(eng):
        vocab = eng.cfg.vocab_size
        eng._sample_token = lambda logits=None: (
            int(np.argmax(logits)) + 1) % vocab

    result = _run(tmp_path, fault)
    assert result["correct"] is False
    assert result["check"]["widest_logit_error"]["value"] > 10 * LIMIT


def test_half_the_decode_batch_reading_no_cache_fails(tmp_path, monkeypatch):
    """The paged decode kernel sees no cached tokens for every other
    request of its batch (row 0 included, so a batch of one is hit)."""
    from repro.kernels import ops

    real = ops.paged_decode_partial

    def broken(q, k_pages, v_pages, block_table, lengths, *a, **kw):
        lengths = np.asarray(lengths).copy()
        lengths[::2] = 0
        return real(q, k_pages, v_pages, block_table, lengths, *a, **kw)

    monkeypatch.setattr(ops, "paged_decode_partial", broken)
    result = _run(tmp_path)
    assert result["correct"] is False


def test_prefill_reading_half_its_prompt_fails(tmp_path):
    """One-token answers, so only the packed prefill serves tokens, and
    its program reads every other prompt token as token 0."""
    def fault(eng):
        ex = eng.executor
        real = ex._packed_prefill_step

        def broken(*bucket):
            fn = real(*bucket)
            return lambda params, tokens, *rest: fn(
                params, tokens.at[1::2].set(0), *rest)

        ex._packed_prefill_step = broken

    result = _run(tmp_path, fault, outs=(1, 1))
    assert result["correct"] is False
