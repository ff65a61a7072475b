"""Paper Figs 1/3/4/5/6 — rank-interference characterization from the
calibrated cost model (and the simulated cluster for Fig 6)."""
from __future__ import annotations

import copy

from repro.cluster import ServerModel, co_serving_slowdown, make_server
from repro.core.types import AdapterInfo
from repro.traces import synth_trace

from .common import emit, sim_cluster, timed


def run():
    rows = []
    # Fig 3: TTFT vs input size per rank (relative to rank-8)
    s = ServerModel(tp=1)
    for inp in (500, 1000, 2000, 4000):
        base, us = timed(s.prefill_time, inp, 8)
        for rank in (16, 32, 64, 128):
            ratio = s.prefill_time(inp, rank) / base
            rows.append(emit(f"fig3/prefill_rel/in{inp}/r{rank}", us,
                             f"rel_ttft={ratio:.2f}"))
    # Fig 3 bottom: TBT is far less rank-sensitive
    tbt_ratio = s.decode_time(16, 128) / s.decode_time(16, 8)
    rows.append(emit("fig3/decode_rel/r128", 0.0,
                     f"rel_tbt={tbt_ratio:.2f}"))
    # Fig 4: model size amplification (TP=8, input 2000)
    for model in ("llama-7b", "llama-30b", "llama-70b"):
        sm = make_server(model, tp=8)
        ratio = sm.prefill_time(2000, 128) / sm.prefill_time(2000, 8)
        rows.append(emit(f"fig4/model_size/{model}", 0.0,
                         f"rel_ttft_r128={ratio:.2f}"))
    # Fig 5: TP sweep (input 2000)
    for tp in (1, 2, 4, 8):
        st = ServerModel(tp=tp)
        ratio = st.prefill_time(2000, 128) / st.prefill_time(2000, 8)
        rows.append(emit(f"fig5/tp{tp}", 0.0,
                         f"rel_ttft_r128={ratio:.2f}"))
    # Fig 1: co-serving tax on the smaller rank
    s4 = ServerModel(tp=4)
    for pair in ((8, 8), (8, 32), (8, 128), (32, 128)):
        tax = co_serving_slowdown(s4, *pair)
        rows.append(emit(f"fig1/coserve/r{pair[0]}_with_r{pair[1]}", 0.0,
                         f"slowdown={tax:.2f}"))
    # Fig 6: single-server Poisson load by rank (P95 TTFT)
    for rank in (8, 32, 128):
        ad = [AdapterInfo(f"a{rank}", rank, 10_000_000)]
        tr = synth_trace(ad, rps=8, duration=120, arrival="poisson",
                         jitter=0.0, seed=5)
        sim = sim_cluster(1, ad, timeout=600, policy="slora-random")
        res, us = timed(lambda: sim.run(copy.deepcopy(tr)), repeat=1)
        rows.append(emit(f"fig6/poisson8rps/r{rank}", us,
                         f"p95_ttft={res.p95_ttft():.3f}s"))
    return rows
