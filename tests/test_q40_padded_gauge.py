"""``dllama_q40_padded_weight_bytes{role}`` at the cells' real shapes:
``engine.weights.q40_padded_bytes`` over the Q40 leaves of a configuration as
the loader builds them at the published widths (shapes only:
tests/q40_leaf_shapes.py). The toy of tests/benchmark/ has no width over 1024,
so its gauge test never met a padded column; PERF.md section 4 has these
numbers.
"""

import pytest

from distributed_llama_tpu.engine.weights import q40_padded_bytes
from distributed_llama_tpu.ops import q40
from tests import q40_leaf_shapes as leaf_shapes

Q40_BYTES = 0.625  # a weight resident: half a byte of nibbles and a float32 scale a block of 32

# role -> (packs in the cell's ten layers, rows held, columns held, the matrix's rows and columns, MB of padding)
GRANITE_SMALL = {
    "experts_gate_up": (10 * 18, 4096, 1536, 4096, 1536, 0.0),  # 2048 columns until PR 51: 236 MB of padding
    "experts_down": (10 * 18, 1024, 4096, 768, 4096, 118.0),  # the contraction of 768 in one input tile of 1024
    "ssm_in": (9, 4096, 17408, 4096, 16768, 14.7),
    "shared_gate_up": (10, 4096, 3072, 4096, 3072, 0.0),
    "wcls": (1, 4096, 25600, 4096, 25088, 1.3),
}


@pytest.fixture(scope="module")
def granite_small():
    return q40_padded_bytes(leaf_shapes.param_shapes("granite-4.0-h-small-q40-10l-ep4"))


@pytest.mark.parametrize("role", sorted(GRANITE_SMALL))
def test_granite_smalls_padding_by_leaf(granite_small, role):
    packs, rows, cols, n, d, megabytes = GRANITE_SMALL[role]
    assert granite_small[role] == round(packs * (rows * cols - n * d) * Q40_BYTES)
    assert round(granite_small[role] / 1e6, 1) == megabytes


def test_granite_smalls_other_leaves_hold_no_padding(granite_small):
    assert {role for role, held in granite_small.items() if held} == {"experts_down", "ssm_in", "wcls"}
    assert set(granite_small) == {"ssm_in", "wo", "qkv", "experts_gate_up", "experts_down",
                                  "shared_gate_up", "shared_down", "wcls"}


def test_glms_qkv_a_is_padded_by_192_columns_not_704():
    """``q_a`` 768 | ``kv_a`` 576 = 1344 columns in 1536 (2048 until PR 51),
    over 2048 rows in each of the cell's nine layers."""
    held = q40_padded_bytes(leaf_shapes.param_shapes("glm-4.7-flash-q40-stage0"))
    assert held["qkv_a"] == round(9 * 2048 * 192 * Q40_BYTES)
    assert held["qkv_a"] < round(9 * 2048 * 704 * Q40_BYTES)


# glm-5-q40-5l-ep16: role -> (packs in the cell's five layers, rows held, columns held, the
# matrix's rows and columns, MB of padding). The index key's and the index heads' weights'
# matrices (6144 -> 128 and 6144 -> 32, narrower than any output tile) stand behind q_a|kv_a in
# ``qkv_a``: 2048 + 576 + 128 + 32 = 2784 columns in 3072 (2816 is 11 tiles of 256 at 256 rows,
# 2944 is 23 of 128); the index heads' queries behind ``q_b``: 16384 + 4096 = 20480, no padding
GLM5 = {
    "qkv_a": (5, 6144, 3072, 6144, 2784, 5.5),
    "q_b": (5, 2048, 20480, 2048, 20480, 0.0),
    "wo": (5, 16384, 6144, 16384, 6144, 0.0),
    "experts_gate_up": (4 * 16, 6144, 4096, 6144, 4096, 0.0),
    "experts_down": (4 * 16, 2048, 6144, 2048, 6144, 0.0),
    "wcls": (1, 6144, 19456, 6144, 19360, 0.4),
}


@pytest.fixture(scope="module")
def glm5():
    return q40_padded_bytes(leaf_shapes.param_shapes("glm-5-q40-5l-ep16"))


@pytest.mark.parametrize("role", sorted(GLM5))
def test_glm5s_padding_by_leaf(glm5, role):
    packs, rows, cols, n, d, megabytes = GLM5[role]
    assert glm5[role] == round(packs * (rows * cols - n * d) * Q40_BYTES)
    assert round(glm5[role] / 1e6, 1) == megabytes


def test_glm5s_indexer_has_no_leaf_of_its_own_and_pads_only_qkv_a(glm5):
    assert {role for role, held in glm5.items() if held} == {"qkv_a", "wcls"}
    assert set(glm5) == {"qkv_a", "q_b", "wo", "gate_up", "down", "experts_gate_up", "experts_down",
                         "shared_gate_up", "shared_down", "wcls"}
    assert q40._d_padded(2784) == 3072 and q40._d_padded(20480) == 20480


@pytest.mark.parametrize("name", leaf_shapes.CONFIGS)
def test_every_q40_leaf_is_named_and_counts_what_its_shape_holds(name):
    params = leaf_shapes.param_shapes(name)
    held, leaves = q40_padded_bytes(params), leaf_shapes.q40_leaves(params)
    assert set(held) == set(leaves)
    for role, packs in leaves.items():
        want = 0
        for p in packs:
            experts = p.qs.shape[0] if len(p.qs.shape) == 3 else 1
            assert (p.n_padded, p.d_padded) == (q40._n_padded(p.n), q40._d_padded(p.d)), role
            want += round(experts * (p.n_padded * p.d_padded - p.n * p.d) * Q40_BYTES)
        assert held[role] == want, role
