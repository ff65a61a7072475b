"""The architecture a configuration names: the dense module gives what
the code gave before it moved there, and a module that a checkout only
adds as a file runs a cell through ``load_cell`` and ``run_cell``."""
import json

import jax
import numpy as np
import pytest

from chipbench import flops as F
from chipbench import harness, weights
from chipbench import scopes as S
from chipbench.harness import ROOT
from chipbench.tests.helpers import DATA, SECONDS, SEED, write_root

dense = harness.architecture(ROOT, "dense")
DENSE_SOURCE = (ROOT / "chipbench" / "arch" / "dense.py").read_text()
PARENT = json.loads((DATA / "dense-parent.json").read_text())
SMOKE = json.loads((DATA / "smoke-dense.json").read_text())


def _digest(tree):
    """Per leaf: sum of |x| in float64, then 6 elements at evenly spaced
    flat indices (as ``dense-parent.json`` records them)."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x, np.float64).ravel()
        idx = np.linspace(0, a.size - 1, 6).astype(int)
        out[jax.tree_util.keystr(path)] = \
            [float(np.abs(a).sum())] + [float(v) for v in a[idx]]
    return out


@pytest.mark.parametrize("case", ["smoke-dense",
                                  "smoke-dense without biases"])
def test_dense_weights_and_reference_are_unchanged(case):
    cfg = dict(SMOKE, qkv_bias=case == "smoke-dense")
    want = PARENT[case]
    aid, rank = PARENT["adapter"]
    seed = PARENT["seed"]
    params = weights.make_params(dense, cfg, seed)
    ad = weights.make_adapter(dense, cfg, seed, aid, rank)
    # every leaf, drawn from the same key splits in the same order
    got = _digest(params)
    assert sorted(got) == sorted(want["params"])
    for leaf, vals in want["params"].items():
        assert got[leaf] == pytest.approx(vals, rel=1e-6, abs=1e-9), leaf
    got = _digest(ad)
    assert sorted(got) == sorted(want["adapter"])
    for leaf, vals in want["adapter"].items():
        assert got[leaf] == pytest.approx(vals, rel=1e-6, abs=1e-9), leaf
    t = PARENT["tokens"]
    toks = (np.arange(t["n"]) * t["mul"] + t["add"]) % cfg["vocab_size"]
    lg = dense.logits(cfg, params, ad, toks)
    assert _digest({"l": lg})["['l']"] == pytest.approx(
        want["logits"], rel=1e-5, abs=1e-6)


@pytest.mark.parametrize("name", sorted(PARENT["counts"]))
def test_dense_counts_are_unchanged(name):
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / f"{name}.json").read_text())
    want = PARENT["counts"][name]
    rows = [tuple(r) for r in PARENT["rows"]["decode"]]
    pre = [tuple(r) for r in PARENT["rows"]["prefill"]]
    assert dense.block_matmul_params(cfg) == want["block_matmul_params"]
    assert dense.weight_bytes(cfg) == want["weight_bytes"]
    assert dense.kv_bytes_per_token(cfg) == want["kv_bytes_per_token"]
    assert F.adapter_params(dense.target_dims, cfg, 16) == \
        want["adapter_params_16"]
    assert list(dense.decode_cost(cfg, rows)) == want["decode_cost"]
    assert dense.prefill_flops(cfg, pre) == want["prefill_flops"]
    assert dense.scope_cost(cfg, rows) == {"lora": tuple(want["lora_cost"])}


def test_frozen_keeps_flat_keys_and_nested_groups():
    flat = {k: v for k, v in SMOKE.items() if not isinstance(v, dict)}
    # the key a flat file had before groups were kept
    before = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                          for k, v in flat.items()))
    assert weights.frozen(flat) == before
    nested = dict(SMOKE, mla={"kv_lora_rank": 512, "dims": [128, 64]})
    key = weights.frozen(nested)
    hash(key)
    assert weights.thawed(key) == nested
    other = dict(nested, mla={"kv_lora_rank": 256, "dims": [128, 64]})
    assert weights.frozen(other) != key


def test_configuration_without_architecture_is_refused(tmp_path):
    root = write_root(tmp_path)
    path = root / "chipbench" / "configs" / "smoke-dense.json"
    cfg = json.loads(path.read_text())
    del cfg["architecture"]
    path.write_text(json.dumps(cfg))
    with pytest.raises(KeyError, match="architecture"):
        harness.load_cell("smoke-dense.smoke-mix", root)


def test_scope_of_takes_a_callers_names():
    names = dense.SCOPES + ("experts", "router")
    assert S.scope_of("jit(_decode)/while/body/mlp/experts/dot_general",
                      names) == "experts"
    assert S.scope_of("jit(_decode)/while/body/router/top_k",
                      names) == "router"
    # a name the caller does not list folds into other
    assert S.scope_of("jit(_decode)/while/body/router/top_k",
                      dense.SCOPES) == "other"


class _Run:
    def __init__(self, profile):
        self.profile = profile


@pytest.mark.parametrize("cell", ["tail", "tput"])
def test_scope_readers(cell):
    prof = {"decode_steps": 4,
            "scope_device_s": {"lora": 0.004, "attention": 0.008,
                               "other": 0.001},
            "scope_least_s": {"lora": 0.001}}
    read = {m: harness.reader(f"{m}.{cell}")
            for m in ("decode_lora_ms", "decode_attention_ms",
                      "lora_roofline")}
    assert read["decode_lora_ms"](_Run(prof)) == pytest.approx(1.0)
    assert read["decode_attention_ms"](_Run(prof)) == pytest.approx(2.0)
    assert read["lora_roofline"](_Run(prof)) == pytest.approx(25.0)
    # nothing to read: no metric, never a 0
    empty = {"decode_steps": 0, "scope_device_s": {}, "scope_least_s": {}}
    for fn in read.values():
        assert fn(_Run(None)) is None
        assert fn(_Run(empty)) is None


def test_decode_program_names_every_scope(tmp_path):
    """The map from the compiled decode program's instructions to scopes
    that a traced run labels device ops with."""
    cell = harness.load_cell("smoke-dense.smoke-mix", write_root(tmp_path))
    params = weights.make_params(cell.arch, cell.config, SEED)
    with weights.served_adapters(cell.arch, cell.config, SEED):
        cluster = harness.build_cluster(cell, params, SEED)
        hlo = harness.decode_hlo(cluster, cell.arch.SCOPES)
    assert set(hlo.values()) == set(cell.arch.SCOPES) | {S.OTHER}


def _renamed_root(tmp_path, source):
    return write_root(tmp_path, config="smoke-copy",
                      cell="smoke-copy.smoke-mix", arch="dense_copy",
                      arch_source=source)


def test_architecture_added_as_a_file_runs_correct(tmp_path):
    cell = harness.load_cell("smoke-copy.smoke-mix",
                             _renamed_root(tmp_path, DENSE_SOURCE))
    assert cell.arch is not dense
    assert cell.arch.__file__.startswith(str(tmp_path))
    res = harness.run_cell(cell, SEED, SECONDS, False)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_added_architecture_without_lora_in_its_reference_fails(tmp_path):
    delta = ' + mm(mm(x, ad[target]["A"]), ad[target]["B"])'
    assert DENSE_SOURCE.count(delta) == 1
    cell = harness.load_cell(
        "smoke-copy.smoke-mix",
        _renamed_root(tmp_path, DENSE_SOURCE.replace(delta, "")))
    res = harness.run_cell(cell, SEED, SECONDS, False)
    assert res["correct"] is False, res["checks"]
