"""Declarative sharding rule table (ISSUE 15, parallel/sharding.py).

* Golden snapshot — every weight leaf of every arch (llama dense,
  Mixtral MoE) x every params layout x representative mesh mappings
  resolves to a pinned PartitionSpec. A rule edit that silently changes
  a leaf's layout fails HERE, loudly, instead of silently resharding a
  405B load. Regenerate deliberately with:
  ``python tests/test_sharding_rules.py --regen``
* Exactly-one-match — unmatched and doubly-matched leaves raise the
  typed errors (never silent replication).
* Skeleton/reality lockstep — the structure-only skeletons the spec
  builders resolve over have exactly the leaf paths of trees the REAL
  builders produce (engine.weights.load_params / random_params /
  stack_expert_leaves), so the table and the loaders cannot drift.

Pure rule-table checks: no shard_map, no devices involved.
"""

import json
import os

import pytest

from distributed_llama_tpu.formats.model_file import ArchType
from distributed_llama_tpu.models.config import LlamaConfig
from distributed_llama_tpu.parallel import sharding
from distributed_llama_tpu.parallel.sharding import (
    AmbiguousLeafError,
    Rule,
    RuleTable,
    UnmatchedLeafError,
)

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "data", "sharding_golden.json")

DENSE_CFG = LlamaConfig(
    arch=ArchType.LLAMA, dim=32, hidden_dim=64, n_layers=2, n_heads=4,
    n_kv_heads=2, vocab_size=64, seq_len=24, head_size=8, kv_dim=16,
)
MOE_CFG = LlamaConfig(
    arch=ArchType.MIXTRAL, dim=32, hidden_dim=64, n_layers=2, n_heads=4,
    n_kv_heads=2, vocab_size=64, seq_len=24, head_size=8, kv_dim=16,
    n_experts=2, n_active_experts=2,
)
CFGS = {"llama": DENSE_CFG, "mixtral": MOE_CFG}

# representative mesh mappings: the classic 1-D tp mesh, the one-process
# ('data','model') pod, and the 2-D (tp, ep) expert mesh
AXES = {
    "tp": {"model": "tp"},
    "pod": {"model": "model"},
    "tp_ep": {"model": "tp", "expert": "ep"},
}

CASES = [
    # (layout, arch, axes key) — every weight leaf of every arch/layout
    ("layered", "llama", "tp"), ("layered", "llama", "pod"),
    ("layered", "mixtral", "tp"), ("layered", "mixtral", "pod"),
    ("stacked", "llama", "tp"), ("stacked", "mixtral", "tp"),
    ("q40", "llama", "tp"), ("q40", "llama", "pod"),
    ("q40", "mixtral", "tp"), ("q40", "mixtral", "pod"),
    ("ep", "mixtral", "tp_ep"), ("ep_q40", "mixtral", "tp_ep"),
]


def resolved_table(layout, arch, axes_key, shard_vocab=True):
    cfg = CFGS[arch]
    table = sharding.param_rules(cfg, layout, shard_vocab)
    skel = sharding.params_skeleton(cfg, layout)
    return table.table(skel, AXES[axes_key])


def build_golden() -> dict:
    out = {}
    for layout, arch, axes_key in CASES:
        key = f"{layout}|{arch}|{axes_key}"
        out[key] = {
            path: str(spec)
            for path, spec in sorted(resolved_table(layout, arch, axes_key).items())
        }
    # the cache/slab/pool table rides the same snapshot
    out["cache|tp"] = {
        kind: str(sharding.cache_spec(kind, {"model": "tp", "seq": "sp"}))
        for kind in sorted(sharding.CACHE_AXES)
    }
    out["cache|pod"] = {
        kind: str(sharding.cache_spec(kind, {"model": "model"}))
        for kind in sorted(sharding.CACHE_AXES)
    }
    return out


class TestGoldenSnapshot:
    def test_every_leaf_matches_exactly_one_rule_and_layout_is_pinned(self):
        with open(GOLDEN_PATH) as f:
            golden = json.load(f)
        got = build_golden()
        assert got == golden, (
            "resolved sharding table drifted from tests/data/"
            "sharding_golden.json — if the layout change is INTENDED, "
            "regenerate with `python tests/test_sharding_rules.py --regen` "
            "and justify the diff in the PR"
        )

    def test_snapshot_is_not_silently_replicated(self):
        """The golden itself must carry real sharding: every layout/arch
        combo shards at least its attention and FFN matmuls."""
        for layout, arch, axes_key in CASES:
            table = resolved_table(layout, arch, axes_key)
            axis = AXES[axes_key]["model"]
            sharded = [p for p, s in table.items() if axis in s]
            assert len(sharded) >= 4, (layout, arch, sharded)


class TestExactlyOneMatch:
    def test_unmatched_leaf_is_a_typed_error(self):
        table = sharding.param_rules(DENSE_CFG, "layered", True)
        skel = sharding.params_skeleton(DENSE_CFG, "layered")
        skel["layers"][0]["mystery_adapter"] = None
        with pytest.raises(UnmatchedLeafError):
            table.resolve(skel, AXES["tp"])

    def test_moe_leaf_under_dense_table_is_unmatched(self):
        """A MoE tree resolved against the dense arch's table fails loudly
        (the silent-replication bug class this exists to kill)."""
        dense_table = sharding.param_rules(DENSE_CFG, "layered", True)
        moe_skel = sharding.params_skeleton(MOE_CFG, "layered")
        with pytest.raises(UnmatchedLeafError):
            dense_table.resolve(moe_skel, AXES["tp"])

    def test_doubly_matched_leaf_is_a_typed_error(self):
        table = RuleTable(
            "broken",
            (
                Rule(r"w", (None, sharding.MODEL)),
                Rule(r"w|x", (sharding.MODEL, None)),
            ),
        )
        with pytest.raises(AmbiguousLeafError):
            table.resolve({"w": None}, AXES["tp"])

    def test_concrete_axis_in_template_is_rejected(self):
        table = RuleTable("broken", (Rule(r"w", (None, "tp")),))
        with pytest.raises(sharding.ShardingRuleError):
            table.resolve({"w": None}, AXES["tp"])


class TestSkeletonMatchesRealTrees:
    """The skeletons the spec builders resolve over must have exactly the
    leaf paths of trees the REAL builders produce."""

    @staticmethod
    def paths(tree):
        return {p for p, _ in sharding.leaf_paths(tree)}

    @pytest.mark.parametrize("arch", ["llama", "mixtral"])
    @pytest.mark.parametrize("layered", [True, False])
    def test_dense_synthetic(self, arch, layered):
        from distributed_llama_tpu.engine import weights as weights_lib

        cfg = CFGS[arch]
        tree = weights_lib.random_params(cfg, layered=layered)
        skel = sharding.params_skeleton(cfg, "layered" if layered else "stacked")
        assert self.paths(tree) == self.paths(skel)

    @pytest.mark.parametrize("arch", ["llama", "mixtral"])
    def test_q40_real_load(self, arch, tmp_path):
        """Through the REAL loader: a synthetic q40 model file read by
        engine.weights.load_params, every leaf matching exactly one rule."""
        from distributed_llama_tpu.engine import weights as weights_lib
        from distributed_llama_tpu.formats.model_file import ModelFileReader
        from distributed_llama_tpu.formats.synthetic import (
            tiny_spec,
            write_synthetic_model,
        )

        kw: dict = {}
        if arch == "mixtral":
            kw = dict(arch_type=ArchType.MIXTRAL, n_experts=2, n_active_experts=2)
        spec = tiny_spec(**kw)
        path = write_synthetic_model(str(tmp_path / "m.m"), spec, seed=1)
        reader = ModelFileReader(path)
        tree = weights_lib.load_params(reader, dtype="q40")
        cfg_loaded = None
        from distributed_llama_tpu.models.config import config_from_spec

        cfg_loaded = config_from_spec(reader.spec)
        reader.close()
        skel = sharding.params_skeleton(cfg_loaded, "q40")
        assert self.paths(tree) == self.paths(skel)
        table = sharding.param_rules(cfg_loaded, "q40", shard_vocab=False)
        resolved = table.resolve(tree, AXES["tp"])  # no typed error = pass
        assert self.paths(resolved) == self.paths(tree)

    def test_ep_stacked_leaves(self, tmp_path):
        from distributed_llama_tpu.engine import weights as weights_lib
        from distributed_llama_tpu.formats.model_file import ModelFileReader
        from distributed_llama_tpu.formats.synthetic import (
            tiny_spec,
            write_synthetic_model,
        )
        from distributed_llama_tpu.models.config import config_from_spec
        from distributed_llama_tpu.parallel.expert_parallel import (
            stack_expert_leaves,
        )

        spec = tiny_spec(
            arch_type=ArchType.MIXTRAL, n_experts=2, n_active_experts=2
        )
        path = write_synthetic_model(str(tmp_path / "m.m"), spec, seed=1)
        reader = ModelFileReader(path)
        cfg_loaded = config_from_spec(reader.spec)
        tree = stack_expert_leaves(weights_lib.load_params(reader, dtype="q40"))
        reader.close()
        skel = sharding.params_skeleton(cfg_loaded, "ep_q40")
        assert self.paths(tree) == self.paths(skel)


class TestBackendLookups:
    """The historical spec builders are now table lookups: pin their
    output shape so backends constructed either way agree."""

    def test_ep_param_specs_roundtrip(self):
        from jax.sharding import PartitionSpec as P

        from distributed_llama_tpu.parallel.expert_parallel import ep_param_specs

        specs = ep_param_specs(MOE_CFG, quantized=True, shard_vocab=False)
        lp = specs["layers"][0]
        assert lp["experts_gate_up"] == P("ep", None, "tp")
        assert lp["experts_down"] == P("ep", "tp", None)
        assert lp["qkv"] == P(None, "tp")
        dense = ep_param_specs(MOE_CFG, quantized=False, shard_vocab=True)
        assert dense["layers"][1]["moe_down"] == P("ep", "tp", None)
        assert dense["wcls"] == P(None, "tp")

    def test_pod_axes_substitute_cleanly(self):
        from jax.sharding import PartitionSpec as P

        from distributed_llama_tpu.parallel.tensor_parallel import (
            param_specs_layered,
            q40_param_specs,
        )

        s = param_specs_layered(DENSE_CFG, 2, True, axis="model")
        assert s["layers"][0]["q"] == P(None, "model")
        assert s["wcls"] == P(None, "model")
        q = q40_param_specs(MOE_CFG, 2, False, axis="model")
        assert q["layers"][0]["experts"][1]["down"] == P("model", None)


if __name__ == "__main__":
    import sys

    if "--regen" in sys.argv:
        os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
        with open(GOLDEN_PATH, "w") as f:
            json.dump(build_golden(), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {GOLDEN_PATH}")
