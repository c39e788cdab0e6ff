"""The program's ``loong.*`` host spans: emitted by a tiny real-mode engine
served by the open-loop driver under the profiler (CPU), nested inside the
driver's ``engine.<kind>`` spans, and read by `spans.reduce` and the three
readers of the scheduler, executor-host and KV-pool layers."""
import glob
import os

import numpy as np
import pytest

import jax

import registry
import spans
import xplane
from benchtools import TINY_MODEL
from driver import OpenLoop, RunRecord, Served
from generator import Planned

SPAN_READERS = ("host_idle_ms", "kv_write_ms", "sched_ms")
PHASES = (".pack", ".launch", ".wait", ".sample")
# what each engine's serve emits: packed prefill then paged decode, or
# chunked unified iterations then paged decode; the packed one also takes a
# checkpoint from an event hook, which downloads the stale KV slots
EXPECTED = {
    "packed": {"loong.schedule", "loong.kv.write", "loong.kv.upload",
               "loong.kv.host_sync"}
    | {"loong.prefill" + p for p in ("",) + PHASES}
    | {"loong.decode" + p for p in ("",) + PHASES},
    "unified": {"loong.schedule", "loong.kv.write", "loong.kv.upload"}
    | {"loong.unified" + p for p in ("",) + PHASES}
    | {"loong.decode" + p for p in ("",) + PHASES},
}


def _engine(mode):
    from repro.configs.base import ModelConfig
    from repro.launch.serve import build_engine
    from repro.manager.scheduler import ManagerConfig
    from repro.models import build_model

    import weights

    cfg = ModelConfig(**TINY_MODEL)
    kw = {}
    if mode == "unified":
        kw["mcfg"] = ManagerConfig(prefill_chunk_tokens=8)
    return build_engine("loongserve", cfg, 2, 64, model=build_model(cfg),
                        params=weights.make(TINY_MODEL, 5), store_values=True,
                        page_size=16, **kw)


def _planned(seed):
    rng = np.random.default_rng(seed)
    return [Planned(0.0, rng.integers(0, 256, n).astype(np.int32), m)
            for n, m in ((9, 3), (20, 4), (33, 2))]


def _cpu_ops(profile):
    """The CPU runs XLA's ops on host threads and has no device plane: its
    executor's op events stand in for one."""
    ops = []
    for plane in profile.planes:
        for line in plane.lines:
            if line.name.startswith("tf_XLA"):
                ops += [(ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns), "")
                        for ev in line.events
                        if not ev.name.startswith("ThreadpoolListener")]
    return {"/device:CPU:0": ops}


@pytest.fixture(scope="module", params=["packed", "unified"])
def served(request, tmp_path_factory):
    """(mode, loop, host, loong spans, CPU ops) of one traced serve, after
    an untraced serve of the same shapes has compiled them."""
    from jax.profiler import ProfileData

    mode = request.param
    eng = _engine(mode)
    loop = OpenLoop(eng, annotate=True)
    for p in _planned(1):
        loop._submit(Served(0.0, len(p.prompt), p.max_new), p)
    while eng.events:
        loop._step()
    if mode == "packed":
        ck = str(tmp_path_factory.mktemp("ck") / "engine.ckpt")
        taken = []

        def checkpoint(e, kind, payload):
            if kind == "prefill_done" and not taken:
                taken.append(kind)
                e.checkpoint(ck)

        eng.event_hooks.append(checkpoint)
    tdir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        loop.run(_planned(2), 4.0)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    with open(path[0], "rb") as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    host, loong, _ = spans.events(profile)
    return mode, loop, host, loong, _cpu_ops(profile)


def test_every_program_span_is_emitted_inside_a_driver_span(served):
    mode, loop, host, loong, _ = served
    assert all(not s.req.output_tokens or s.req.finish_time is not None
               for s in loop.by_req.values())
    assert {n for n, *_ in loong} == EXPECTED[mode]
    drv = [(s, e) for n, s, e, _ in host if n.startswith("engine.")]
    for name, s, e, _ in loong:
        assert any(a <= s and e <= b for a, b in drv), name
    args = {n: a for n, _, _, a in loong}
    step = "loong.prefill" if mode == "packed" else "loong.unified"
    assert {"n_req", "rid0", "rid1", "tokens", "dop"} <= set(args[step])
    assert {"pending", "launched"} <= set(args["loong.schedule"])
    assert {"instances", "slots"} <= set(args["loong.kv.write"])
    assert args["loong.kv.upload"]["slots"] > 0


def test_no_program_span_takes_the_driver_prefixes(served):
    """The driver's spans are its own: one ``engine.<kind>`` per handled
    event and one window, so `xplane` reads exactly what it read before."""
    _, loop, host, loong, _ = served
    assert all(n.startswith(spans.PREFIX) for n, *_ in loong)
    assert sum(n.startswith("engine.") for n, *_ in host) == len(loop.events)
    assert [n for n, *_ in host if n.startswith("bench.")] == ["bench.window"]


def test_span_readers_read_the_served_trace(served):
    mode, loop, host, loong, ops = served
    out = spans.reduce(host, loong, ops)
    assert xplane.reduce(host, ops) == {
        k: v for k, v in out.items() if k in xplane.reduce(host, ops)}
    rec = RunRecord(4.0, TINY_MODEL, {}, [], [], loop.events)
    rec.trace = out
    if mode == "packed":
        assert out["prefilled"] == 3 and out["kv_write_s"] > 0
        for name in SPAN_READERS:
            v = registry.metric(name)(rec)
            assert v is not None and v >= 0.0, name
    else:  # no packed prefill: nothing is counted as prefilled
        assert out["prefilled"] == 0
        assert all(registry.metric(n)(rec) is None for n in SPAN_READERS)
    assert out["sched_s"] > 0 and out["loong_spans"] == len(loong)
    assert sum(out["idle_by_span"].values()) == pytest.approx(
        out["window_s"] - out["busy_s"], abs=1e-6)


# ----------------------------------------------------- synthetic timelines
MS = 1_000_000
PK = ('%prefill_ring_chunk_attn.3 = f32[8] custom-call(s32[2] %a), '
      'custom_call_target="tpu_custom_call", frontend_attributes='
      '{kernel_metadata={\n"kernel":"prefill_ring_chunk_attn"\n}}')


def _timeline():
    host = [("bench.window", 0, 100 * MS, None),
            ("engine.prefill_done", 0, 60 * MS, 0),
            ("engine.arrival", 70 * MS, 72 * MS, 1)]
    loong = [("loong.schedule", 65 * MS, 66 * MS, {}),  # outside a driver span
             ("loong.prefill", 1 * MS, 58 * MS, {"n_req": 2}),
             ("loong.prefill.pack", 1 * MS, 5 * MS, {}),
             ("loong.prefill.launch", 5 * MS, 6 * MS, {}),
             ("loong.prefill.wait", 6 * MS, 40 * MS, {}),
             ("loong.kv.write", 42 * MS, 50 * MS, {}),
             ("loong.kv.upload", 44 * MS, 46 * MS, {}),
             ("loong.schedule", 58 * MS, 59 * MS, {})]
    dev = {"/device:TPU:0": [
        ("fusion.1", 3 * MS, 4 * MS, "jit_prefill_packed_step(1)"),
        (PK, 6 * MS, 38 * MS, "jit_prefill_packed_step(1)"),
        ("fusion.2", 45 * MS, 52 * MS, ""),  # starts in kv.write, ends past it
        ("fusion.3", 55 * MS, 56 * MS, "")]}
    return host, loong, dev


def test_xplane_reads_the_same_with_and_without_program_spans():
    host, loong, dev = _timeline()
    plain = xplane.reduce(host, dev)
    mixed = xplane.reduce(host + [(n, s, e, None) for n, s, e, _ in loong],
                          dev)
    assert mixed == plain
    out = spans.reduce(host, loong, dev)
    assert {k: out[k] for k in plain} == plain


def test_idle_is_split_by_the_innermost_program_span():
    out = spans.reduce(*_timeline())
    # busy [3,4] [6,38] [45,52] [55,56]: 41 ms of 100; idle [0,3] [4,6]
    # [38,45] [52,55] [56,100], in ms by label:
    pd = "engine.prefill_done"
    want = {pd: 1 + 1,  # [0,1] [59,60]
            pd + ">loong.prefill.pack": 2 + 1,  # [1,3] [4,5]
            pd + ">loong.prefill.launch": 1,  # [5,6]
            pd + ">loong.prefill.wait": 2,  # [38,40]
            pd + ">loong.prefill": 2 + 3 + 2,  # [40,42] [52,55] [56,58]
            pd + ">loong.kv.write": 2,  # [42,44]
            pd + ">loong.kv.upload": 1,  # [44,45]
            pd + ">loong.schedule": 1,  # [58,59]
            "driver.wait": 5 + 4 + 28,  # [60,65] [66,70] [72,100]
            "driver.wait>loong.schedule": 1,  # [65,66]
            "engine.arrival": 2}  # [70,72]
    assert out["busy_s"] == pytest.approx(0.041)
    got = {k: round(v * 1e3, 6) for k, v in out["idle_by_span"].items()}
    assert got == {k: float(v) for k, v in want.items()}
    assert out["loong_idle_s"] == pytest.approx(0.018)
    # each gap named at its midpoint, as xplane names it
    assert out["idle_gaps_named"] == [
        ["driver.wait", pytest.approx(0.044)],
        [pd + ">loong.prefill", pytest.approx(0.007)],
        [pd + ">loong.prefill.pack", pytest.approx(0.003)],
        [pd + ">loong.prefill", pytest.approx(0.003)],
        [pd + ">loong.prefill.launch", pytest.approx(0.002)]]
    assert [n for n, _ in out["idle_gaps"]] == [
        "driver.wait"] + [pd] * 4


def test_kv_write_runs_to_its_last_device_op():
    out = spans.reduce(*_timeline())
    assert out["kv_write_s"] == pytest.approx(0.010)  # [42, 52]
    assert out["sched_s"] == pytest.approx(0.002)
    assert out["prefilled"] == 2
    assert out["loong_spans"] == 8


def test_a_pallas_op_is_named_by_its_kernel_metadata():
    out = spans.reduce(*_timeline())
    assert out["device_ops_named"][0] == [
        "jit_prefill_packed_step:prefill_ring_chunk_attn[pallas]",
        pytest.approx(0.032)]
    # xplane's own names stay as they were
    assert dict(out["device_ops"])[
        "jit_prefill_packed_step:%prefill_ring_chunk_attn.3[pallas]"] == \
        pytest.approx(0.032)
    escaped = 'kernel_metadata="{\\"kernel\\":\\"paged_decode_attn\\"}"'
    assert spans.kernel_name(escaped) == "paged_decode_attn"
    assert spans.kernel_name("kernel_metadata={}") is None
    unnamed = 'custom_call_target="tpu_custom_call", kernel_metadata={}'
    assert spans.label("%closed_call.5 = " + unnamed, "jit_step(9)") == \
        "jit_step:%closed_call.5[pallas]"


def test_innermost_follows_nesting_and_order():
    pieces = spans.innermost([("a", 0, 10), ("b", 2, 5), ("c", 5, 7),
                              ("d", 12, 15), ("e", 12, 13)])
    assert pieces == [(0, 2, "a"), (2, 5, "b"), (5, 7, "c"), (7, 10, "a"),
                      (12, 13, "e"), (13, 15, "d")]


def test_span_readers_on_synthetic_events():
    rec = RunRecord(1.0, TINY_MODEL, {}, [], [], [])
    rec.trace = {"loong_idle_s": 0.03, "kv_write_s": 0.012, "sched_s": 0.0015,
                 "prefilled": 3}
    get = lambda n: registry.metric(n)(rec)  # noqa: E731
    assert get("host_idle_ms") == pytest.approx(10.0)
    assert get("kv_write_ms") == pytest.approx(4.0)
    assert get("sched_ms") == pytest.approx(0.5)


def test_span_readers_return_nothing_without_their_source():
    rec = RunRecord(1.0, TINY_MODEL, {}, [], [], [])
    for trace in (None, {"busy_s": 0.5, "window_s": 1.0},
                  {"loong_idle_s": 0.1, "kv_write_s": 0.1, "sched_s": 0.1,
                   "prefilled": 0}):
        rec.trace = trace
        for name in SPAN_READERS:
            assert registry.metric(name)(rec) is None, (name, trace)
