"""Names inside a serving step: scope time of the decode program, the
program's spans as idle-gap labels, the host-gap and poll readings,
the LoRA work's count, and the clock mapping between the program's
spans and the profiler."""
import json
import time

import pytest

from chipbench import flops as F
from chipbench import scopes as S
from chipbench import trace
from chipbench.harness import ROOT, architecture
from repro.obs import Span, Tracer

dense = architecture(ROOT, "dense")


def _span(name, start, end, cat="step", track="server:0"):
    return Span(name, start, end, cat=cat, track=track)


def test_scope_of_takes_the_innermost_name():
    names = dense.SCOPES
    assert S.scope_of("jit(_decode)/while/body/proj/lora/bsd,bdr->bsr/"
                      "dot_general", names) == "lora"
    assert S.scope_of("jit(_decode)/while/body/attention/add",
                      names) == "attention"
    assert S.scope_of("jit(_decode)/lm_head/dot_general", names) == \
        "lm_head"
    assert S.scope_of("jit(_decode)/while/body/dynamic_slice", names) == \
        "other"
    # a name is a whole path component, not a substring
    assert S.scope_of("jit(_decode)/projection/mlps", names) == "other"


def test_hlo_scopes_reads_every_computation():
    text = "\n".join([
        "%body.1 (p: f32[4]) -> f32[4] {",
        '  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(_decode)/while/body/mlp/mul" '
        'source_file="x.py"}',
        '  ROOT %dot.3 = f32[4]{0} dot(%a, %b), metadata={op_name='
        '"jit(_decode)/while/body/proj/lora/dot_general"}',
        "}",
        "ENTRY %main (x: f32[4]) -> f32[4] {",
        '  %copy.2 = f32[4]{0} copy(%x)',
        '  ROOT %dot.9 = f32[4]{0} dot(%x, %x), metadata={op_name='
        '"jit(_decode)/lm_head/dot_general"}',
        "}"])
    hlo = S.hlo_scopes(text, dense.SCOPES)
    assert hlo == {"fusion.7": "mlp", "dot.3": "lora", "dot.9": "lm_head"}
    # a v5e op event is named by its instruction's HLO text
    assert S.event_scope("%dot.3 = f32[4]{0:T(128)} dot(f32[4] %a, "
                         "f32[4] %b)", hlo) == "lora"
    assert S.event_scope("%copy.2 = f32[4]{0} copy(%x)", hlo) is None


def _kept():
    # one decode run [0, 100) whose while op (unnamed) holds the layer's
    # ops, a prefill run [120, 150), and an op outside any decode run
    return {"mark_ns": 0, "devices": [{"name": "/device:TPU:0", "lines": {
        "XLA Ops": [["other", 0, 100],          # the while loop
                    ["proj", 5, 20], ["lora", 25, 10], ["attention", 40, 30],
                    ["mlp", 70, 25],
                    ["lm_head", 125, 20]],     # inside the prefill run
        "XLA Modules": [["jit__decode(7)", 0, 100],
                        ["jit__prefill(3)", 120, 30]]}}]}


def test_decode_scopes_of_a_hand_built_trace():
    got = S.decode_scopes(_kept(), (0, 200))
    assert got == pytest.approx({"other": 15e-9, "proj": 20e-9,
                                 "lora": 10e-9, "attention": 30e-9,
                                 "mlp": 25e-9})
    # the traced span cuts the decode run: ops count only inside it
    assert S.decode_scopes(_kept(), (30, 60)) == pytest.approx(
        {"other": 5e-9, "lora": 5e-9, "attention": 20e-9})
    # labels change nothing the module-level reduction reads
    red = trace.reduce(_kept(), (0, 200))
    assert red["busy_s"] == pytest.approx(120e-9)
    assert red["device_ops"] == [["jit__decode", pytest.approx(100e-9)],
                                 ["jit__prefill", pytest.approx(30e-9)]]


def test_gap_labels_are_innermost_program_spans():
    kept = {"mark_ns": 0, "devices": [{"name": "/device:TPU:0", "lines": {
        "XLA Ops": [["proj", 0, 10], ["proj", 30, 10], ["mlp", 55, 5],
                    ["mlp", 90, 10]],
        "XLA Modules": [["jit__decode(1)", 0, 100]]}}]}
    # spans in seconds on the tracer clock; origin 0, no offset, 1 ns
    # = 1e-9 s
    n = 1e-9
    spans = [
        _span("poll", 0, 100 * n, track="control"),
        _span("poll.step", 5 * n, 95 * n, track="control"),
        _span("engine.step", 5 * n, 80 * n),
        _span("decode", 20 * n, 50 * n, cat="iteration"),
        _span("decode.dispatch", 20 * n, 25 * n),
        _span("decode.sync", 25 * n, 50 * n),
        _span("decode.tokens", 50 * n, 70 * n),
        _span("request", 0, 100 * n, cat="request", track="requests"),
    ]
    labels = S.gap_labels(spans, 0, 0.0)
    assert [lab for lab, _, _ in labels][:2] == ["decode.dispatch server:0",
                                                 "decode.tokens server:0"]
    assert all(not lab.startswith("request") for lab, _, _ in labels)
    red = trace.reduce(kept, (0, 100), labels)
    # each gap takes the innermost span that holds its midpoint
    assert red["idle_gaps"] == [
        ["engine.step server:0", pytest.approx(30e-9)],
        ["decode.dispatch server:0", pytest.approx(20e-9)],
        ["decode.sync server:0", pytest.approx(15e-9)]]


def test_host_gap_and_poll_readings():
    spans = [
        # engine 0: a prefill, its merge, then a decode
        _span("prefill.dispatch", 1.0, 1.1),
        _span("prefill.sync", 1.1, 1.5),
        _span("prefill.merge", 1.5, 1.6),
        _span("decode.dispatch", 2.0, 2.1),
        _span("decode.sync", 2.1, 3.0),
        _span("decode", 2.0, 3.0, cat="iteration"),
        # engine 1: one decode
        _span("decode.dispatch", 3.5, 3.6, track="server:1"),
        _span("decode.sync", 3.6, 4.0, track="server:1"),
        _span("decode", 3.5, 4.0, cat="iteration", track="server:1"),
        _span("poll", 0.5, 3.0, track="control"),
        _span("poll", 3.0, 4.5, track="control"),
        _span("poll", 9.0, 9.1, track="control"),      # after the window
    ]
    # outstanding [1.0, 3.0] (the merge holds until the decode's sync)
    # and [3.5, 4.0]; the window [0, 5] leaves 2.5 s over 2 steps
    assert S.host_gap_ms(spans, (0.0, 5.0)) == pytest.approx(1250.0)
    assert S.host_gap_ms(spans, (5.0, 6.0)) is None
    assert S.poll_p95_ms(spans, (0.0, 5.0)) == pytest.approx(
        1e3 * (1.5 + 0.95 * (2.5 - 1.5)))


def test_lora_cost_matches_hand_counts():
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / "internlm2-1.8b.json").read_text())
    # A+B per unit rank per layer: q and o 2048+2048, k and v 2048+1024
    per = (2048 + 2048) + 2 * (2048 + 1024) + (2048 + 2048)
    rows = [("a", 8, 100), ("b", 16, 50), ("a", 8, 20)]
    (flops, nbytes), = dense.scope_cost(cfg, rows).values()
    assert flops == 2 * 24 * per * (8 + 16 + 8)
    assert nbytes == 4 * 24 * per * (8 + 16)
    # a few rows' LoRA work is bound by its bytes
    peak = {"flops": 197e12, "hbm_bw": 819e9}
    assert F.least_seconds(flops, nbytes, peak) == nbytes / 819e9


def test_recorded_v5e_trace_keeps_its_readings():
    kept = json.loads((ROOT / "chipbench" / "tests" / "data"
                       / "trace-v5e-skew-drift.json").read_text())
    lo, hi = kept["span_ns"]
    before = trace.reduce(kept, (lo, hi))
    labelled = json.loads(json.dumps(kept))
    for op in labelled["devices"][0]["lines"]["XLA Ops"]:
        op[0] = "mlp"
    after = trace.reduce(labelled, (lo, hi))
    assert after["busy_s"] == before["busy_s"]
    assert after["device_ops"] == before["device_ops"]
    # unlabelled ops are all "other", and scope time never exceeds the
    # decode program's own
    got = S.decode_scopes(kept, (lo, hi))
    assert set(got) == {"other"}
    assert 0 < got["other"] <= before["modules_s"]["jit__decode"] * (
        1 + 1e-9)
    assert S.decode_scopes(labelled, (lo, hi))["mlp"] == got["other"]


def test_spans_land_on_the_profiler_clock(tmp_path):
    """A program span and a profiler annotation around the same work,
    mapped through the ``chipbench.mark`` event, within 1 ms."""
    import jax
    from jax.profiler import ProfileData
    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        mark_mono_ns = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(trace.MARK):
            pass
        t0 = tracer.now()
        with jax.profiler.TraceAnnotation("chipbench.test.work"):
            time.sleep(0.02)
        tracer.record("work", t0, tracer.now())
    finally:
        jax.profiler.stop_trace()
    kept = trace.load(str(tmp_path))
    offset = kept["mark_ns"] - mark_mono_ns
    (_, a, b), = S.gap_labels(tracer.spans, tracer.origin_ns, offset)
    path, = tmp_path.glob("**/*.xplane.pb")
    events = [ev for plane in ProfileData.from_file(str(path)).planes
              for line in plane.lines for ev in line.events
              if ev.name == "chipbench.test.work"]
    assert len(events) == 1
    ev = events[0]
    assert abs(a - ev.start_ns) < 1e6
    assert abs(b - (ev.start_ns + ev.duration_ns)) < 1e6
