"""A smoke-size cell for the CPU tests: the configuration and mix in
``data/``, reporting the metrics of the benchmark's open-loop cell."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench import harness

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 31 + 17          # above 32 signed bits, as run seeds may be
SECONDS = 4.0


def write_root(tmp: Path, *, config="smoke-dense", traffic="smoke-mix",
               cell="smoke-dense.smoke-mix", arch=None,
               arch_source=None) -> Path:
    """A checkout-shaped directory that adds one cell from a new
    configuration file and a new traffic file, metrics copied from the
    benchmark's own ``BENCHMARK.json``, beside the benchmark's own
    architecture modules. With ``arch``, the configuration names the
    architecture ``arch``, whose module ``chipbench/arch/<arch>.py`` is
    added: ``arch_source``."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    like = bench["workloads"][0]["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", [like]):
            m["workloads"] = [cell]
    bench["configs"] = [{"name": config, "source": "test",
                         "file": f"chipbench/configs/{config}.json",
                         "reduced": [], "why": "CPU test"}]
    bench["workloads"] = [{"name": cell, "config": config,
                           "traffic": traffic, "chips": 1,
                           "why": "CPU test"}]
    (tmp / "chipbench" / "configs").mkdir(parents=True)
    (tmp / "chipbench" / "traffic").mkdir(parents=True)
    shutil.copytree(harness.ROOT / "chipbench" / "arch",
                    tmp / "chipbench" / "arch",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((DATA / "smoke-dense.json").read_text())
    if arch is not None:
        cfg["architecture"] = arch
        (tmp / "chipbench" / "arch" / f"{arch}.py").write_text(arch_source)
    (tmp / "chipbench" / "configs" / f"{config}.json").write_text(
        json.dumps(cfg))
    shutil.copy(DATA / "smoke-mix.json",
                tmp / "chipbench" / "traffic" / f"{traffic}.json")
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def smoke_cell(tmp: Path) -> harness.Cell:
    return harness.load_cell("smoke-dense.smoke-mix", write_root(tmp))
