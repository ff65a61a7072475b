"""The one traffic generator. A mix is a data file,
``chipbench/traffic/<name>.json``; this module turns it and a run seed
into a schedule of requests.

The shape of the work (arrival times, adapters, prompt and output
lengths) is drawn from the mix's own ``shape_seed``, so every run seed
serves the same work. The run seed changes what is served: the prompt
tokens, and the order of the (prompt length, output length) pairs
among the requests of one adapter inside one ``block_s`` stretch of
the schedule (the mix's ``reorder_block_s``). Per-adapter demand in each stretch is therefore the same
for every seed, and so is what the placement sees.

Popularity follows the paper's production trace (a copy of
``repro.traces.production.production_trace_with_meta``): per rank, a
head adapter takes ``head_share`` of the rank's traffic, drifting with
the Fig 10 shapes, and the rest of the rank's adapters share the
remainder by a Zipf law.
"""
from __future__ import annotations

import dataclasses
import json
import math
import random
from pathlib import Path
from typing import Dict, List, Optional

DRIFTS = ("rising", "falling", "diurnal", "stable", "surge")


@dataclasses.dataclass
class Planned:
    """One request of the schedule, before it becomes a ServeRequest."""
    idx: int
    t: float                  # scheduled arrival, seconds on the run clock
    adapter_id: str
    rank: int
    prompt_len: int
    output_len: int
    prompt: List[int] = dataclasses.field(default_factory=list)


def load_mix(path) -> dict:
    with open(path) as f:
        return json.load(f)


def adapters_of(mix: dict) -> Dict[str, int]:
    """``{adapter_id: rank}`` in the mix's order: ranks ascending, the
    first adapter of each rank is its head."""
    out = {}
    for rank, count in sorted(((int(r), int(c)) for r, c
                               in mix["adapters"].items())):
        for i in range(count):
            out[f"r{rank}-a{i}"] = rank
    return out


def drift(pattern: str, progress: float) -> float:
    """Relative intensity of a head adapter over the run (Fig 10)."""
    if pattern == "rising":
        return 0.5 + progress
    if pattern == "falling":
        return 1.5 - progress
    if pattern == "diurnal":
        return 1.0 + 0.6 * math.sin(2 * math.pi * progress)
    if pattern == "surge":
        return 1.0 if progress < 0.8 else 3.0
    return 1.0


def _length(rng: random.Random, spec: dict) -> int:
    x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    n = int(min(max(x, spec["min"]), spec["max"]))
    step = spec.get("round_up", 1)
    return min(spec["max"], -(-n // step) * step)


def _pick_adapter(rng, by_rank, share, head_share, pattern, progress):
    """(adapter id, rank, total intensity) at ``progress`` of the run."""
    ranks = sorted(by_rank)
    w = [share[r] * ((1 - head_share) + head_share
                     * drift(pattern[r], progress)) for r in ranks]
    rank = rng.choices(ranks, weights=w)[0]
    pool = by_rank[rank]
    head_w = head_share * drift(pattern[rank], progress)
    tail_w = 1 - head_share
    if len(pool) == 1 or rng.random() < head_w / (head_w + tail_w):
        return pool[0], rank, sum(w)
    tail = pool[1:]
    zipf = [1.0 / (j + 1) for j in range(len(tail))]
    return rng.choices(tail, weights=zipf)[0], rank, sum(w)


def schedule(mix: dict, *, seed: int, warmup_s: float, window_s: float,
             vocab_size: int, block_s: Optional[float] = None
             ) -> List[Planned]:
    """Every request of a run: arrivals over ``[0, warmup_s + window_s)``
    for an open loop (``arrivals.kind == "poisson"``), or a backlog of
    ``arrivals.count`` requests all due at time 0 (``"backlog"``)."""
    shape = random.Random(mix["shape_seed"])
    ranks = adapters_of(mix)
    by_rank: Dict[int, List[str]] = {}
    for aid, r in ranks.items():
        by_rank.setdefault(r, []).append(aid)
    share = {int(r): float(s) for r, s in
             mix["popularity"]["rank_share"].items()}
    head_share = float(mix["popularity"]["head_share"])
    drifting = bool(mix["popularity"].get("drift", False))
    # each rank's head drifts with one of the Fig 10 shapes, in rank order
    pattern = {r: (DRIFTS[j % len(DRIFTS)] if drifting else "stable")
               for j, r in enumerate(sorted(by_rank))}
    arr = mix["arrivals"]
    span = warmup_s + window_s
    plans: List[Planned] = []
    if arr["kind"] == "poisson":
        # thinning against the peak intensity of the drifting heads
        grid = [p / 100 for p in range(101)]
        peak = max(_pick_adapter(random.Random(0), by_rank, share,
                                 head_share, pattern, p)[2] for p in grid)
        rate = float(arr["rate_rps"])
        t = 0.0
        while True:
            t += shape.expovariate(rate)
            if t >= span:
                break
            progress = t / span
            aid, rank, w = _pick_adapter(shape, by_rank, share, head_share,
                                         pattern, progress)
            # thinning keeps the mean rate at rate_rps * w/peak
            if shape.random() >= w / peak:
                continue
            plans.append(Planned(len(plans), t, aid, rank,
                                 _length(shape, mix["prompt"]),
                                 _length(shape, mix["output"])))
    elif arr["kind"] == "backlog":
        for _ in range(int(arr["count"])):
            aid, rank, _ = _pick_adapter(shape, by_rank, share, head_share,
                                         pattern, 0.0)
            plans.append(Planned(len(plans), 0.0, aid, rank,
                                 _length(shape, mix["prompt"]),
                                 _length(shape, mix["output"])))
    else:
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    _shuffle_lengths(plans, random.Random(seed), block_s or span)
    rng = random.Random(seed ^ 0x5EED)
    for p in plans:
        p.prompt = [rng.randrange(1, vocab_size)
                    for _ in range(p.prompt_len)]
    return plans


def _shuffle_lengths(plans: List[Planned], rng: random.Random,
                     block_s: float) -> None:
    groups: Dict[tuple, List[Planned]] = {}
    for p in plans:
        groups.setdefault((int(p.t // block_s), p.adapter_id), []).append(p)
    for key in sorted(groups):
        g = groups[key]
        pairs = [(p.prompt_len, p.output_len) for p in g]
        rng.shuffle(pairs)
        for p, (pl, ol) in zip(g, pairs):
            p.prompt_len, p.output_len = pl, ol


def mix_path(root: Path, name: str) -> Path:
    return root / "chipbench" / "traffic" / f"{name}.json"
