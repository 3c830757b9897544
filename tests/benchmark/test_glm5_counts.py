"""GLM-5's byte and operation counts against ISSUE 53's arithmetic made by
hand: a layer's parts, the file, the chip's memory against the driver's floor,
the decode step's floor (index keys of every visible position, latent rows of
the selected ones), and every role the configuration launches."""

import json
import os

import pytest

from benchmark import families

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "benchmark", "configs", "glm-5-q40-5l-ep16.json")) as f:
    CONFIG = json.load(f)
counts = families.counts(CONFIG)
Q40 = 18 / 32
H, QR, LATENT, Q, KV_UP, O, DENSE, WIDTH, VOCAB = 6144, 2048, 576, 16384, 28672, 16384, 12288, 2048, 19360
INDEX_Q, INDEX_K, INDEX_W = 4096, 128, 32


def test_a_layers_parts_by_hand():
    w = counts.layer_weights(CONFIG)
    # ISSUE 53: "attention 165.0 M weights a layer (12.6 + 33.6 + 3.5 + 14.7 + 100.7)"
    parts = [H * QR, QR * Q, H * LATENT, 512 * KV_UP, O * H]
    assert [round(p / 1e6, 1) for p in parts] == [12.6, 33.6, 3.5, 14.7, 100.7]
    assert w["attention"] == sum(parts) and round(w["attention"] / 1e6, 1) == 165.0
    # "indexer 9.4 M (8.39 + 0.79 + 0.20)"
    assert [round(p / 1e6, 2) for p in (QR * INDEX_Q, H * INDEX_K, H * INDEX_W)] == [8.39, 0.79, 0.20]
    assert round(w["indexer"] / 1e6, 1) == 9.4
    # "shared expert 37.7 M, router 1.6 M: 213.7 M outside the routed experts; an expert 37.75 M"
    assert round(w["shared"] / 1e6, 1) == 37.7 and round(w["router"] / 1e6, 1) == 1.6
    outside = w["attention"] + w["indexer"] + w["shared"] + w["router"]
    assert round(outside / 1e6, 1) == 213.7 and round(w["expert"] / 1e6, 2) == 37.75
    # "a dense layer 400.9 M", an expert layer of this chip 817.7 M, of the model 9.66 B + 213.7 M
    assert round((w["attention"] + w["indexer"] + w["dense"]) / 1e6, 1) == 400.9
    assert round((outside + 16 * w["expert"]) / 1e6, 1) == 817.7
    assert round(256 * w["expert"] / 1e9, 2) == 9.66
    # the whole model: 3 dense layers, 75 expert layers, embedding and head 951.6 M each: 744 B
    whole = 3 * (w["attention"] + w["indexer"] + w["dense"]) + 75 * (outside + 256 * w["expert"]) + 2 * H * 154880
    assert round(H * 154880 / 1e6, 1) == 951.6 and round(whole / 1e9) == 744
    assert round(whole * Q40 / 1e9) == 418  # ISSUE 53's "419 GB of Q40 file", to its rounding


def test_the_file_is_2_61_gb():
    # "(400.9 + 4 x 817.7 + 118.9) M x 0.5625 B + 476 MB of f32 embedding = 2.61 GB"
    assert round(H * VOCAB / 1e6, 1) == 118.9 and round(4 * H * VOCAB / 1e6) == 476
    assert counts.file_bytes(CONFIG) == pytest.approx((400.9e6 + 4 * 817.7e6 + 118.9e6) * Q40 + 476e6, rel=1e-3)
    assert round(counts.file_bytes(CONFIG) / 1e9, 2) == 2.61
    # the program's own layout of the file says the same to a thousandth (it counts norms and biases)
    from distributed_llama_tpu.formats.model_file import tensor_layout

    spec = families.load(CONFIG, "modelfile").model_spec(CONFIG, 16384)
    assert sum(e.nbytes for e in tensor_layout(spec)) == pytest.approx(counts.file_bytes(CONFIG), rel=1e-3)


def test_the_cell_is_over_the_drivers_floor_by_arithmetic():
    """A quarter of one chip's 16 GB, by what the cell's flags hold resident
    (no temporary counted): the matrices at 0.625 B a weight (nibbles and a
    float32 scale a block), the float32 embedding, 8 slab rows of 16384
    positions and 3072 pool pages of 64, each position a latent row and an
    index key in bfloat16 over 5 layers."""
    with open(os.path.join(REPO, "benchmark", "workloads", "glm-5.doc_sessions.json")) as f:
        flags = json.load(f)["flags"]
    flag = lambda name: int(flags[flags.index(name) + 1])
    weights = (counts.file_bytes(CONFIG) - 4 * H * VOCAB) / Q40 * 0.625
    per_position = 5 * (LATENT + INDEX_K) * 2
    assert per_position == 5 * 1408
    slab = flag("--parallel") * flag("--max-seq-len") * per_position
    pool = flag("--kv-pages") * 64 * per_position
    assert (round(weights / 1e9, 2), round(4 * H * VOCAB / 1e9, 2)) == (2.37, 0.48)
    assert (round(slab / 1e9, 2), round(pool / 1e9, 2)) == (0.92, 1.38)
    resident = weights + 4 * H * VOCAB + slab + pool
    assert round(resident / 1e9, 1) == 5.2 and resident > 0.25 * 16e9


def test_a_decode_step_by_hand():
    touched = counts.experts_touched(16, 256, 8, 8)
    assert touched == pytest.approx(16 * (1 - (1 - 1 / 32) ** 8)) and 3.5 < touched < 3.6
    w = counts.layer_weights(CONFIG)
    q40 = (5 * (w["attention"] + w["indexer"]) + w["dense"]
           + 4 * (w["router"] + w["shared"] + touched * w["expert"]) + H * VOCAB) * Q40
    got = counts.weight_bytes_per_step(CONFIG, rows=8)
    assert q40 < got < q40 * 1.002  # + the f32 tensors and 8 embedding rows
    assert 1.0e9 < got < 1.2e9  # ISSUE 53's "about 1.2 GB" counts 0.625 B a weight resident
    assert counts.latent_bytes_per_position(CONFIG) == 5 * 1152
    assert counts.index_bytes_per_position(CONFIG) == 5 * 256
    # 8 rows at 7.5k: every index key, 2048 latent rows a row and layer
    step = counts.decode_step_bytes(CONFIG, 8, 8 * 7500)
    assert step == pytest.approx(got + 8 * 7500 * 5 * 256 + 8 * 2048 * 5 * 1152)
    # ISSUE 53: "a layer, 34 MB of index keys and selected latents where a dense scan would read 69 MB"
    assert (step - got) / 5 == pytest.approx(34e6, rel=0.02) and 8 * 7500 * 1152 == pytest.approx(69e6, rel=0.01)
    # a step whose rows are all short of index_topk reads every latent row it sees
    short = counts.decode_step_bytes(CONFIG, 8, 8 * 1000)
    assert short == pytest.approx(got + 8 * 1000 * 5 * (256 + 1152))
    # ... and the floor can only be under what a masked pass reads (every visible latent row)
    assert step < got + 8 * 7500 * 5 * (256 + 1152)


@pytest.mark.parametrize("role,shape,d_in,d_held", [
    ("wqkv", [8, 3072], H, QR + LATENT + INDEX_K + INDEX_W), ("mla_project", [8, 20480], QR, Q + INDEX_Q),
    ("wo", [8, 6144], O, H), ("gate_up", [8, 24576], H, 2 * DENSE), ("gate_up", [8, 4096], H, 2 * WIDTH),
    ("logits", [8, 19456], H, VOCAB), ("wqkv", [256, 3072], H, QR + LATENT + INDEX_K + INDEX_W)])
def test_a_dense_launch_reads_its_matrix_once(role, shape, d_in, d_held):
    nbytes, ops = counts.kernel_launch(CONFIG, role, shape)
    rows = shape[0]
    assert nbytes == pytest.approx(d_in * d_held * Q40 + rows * d_in + 4 * rows * shape[1])
    assert ops == 2.0 * rows * d_in * d_held


def test_the_two_down_matrices_of_one_name_count_as_their_mean_by_launches():
    nbytes, ops = counts.kernel_launch(CONFIG, "down", [8, 6144])
    one = lambda d_in: d_in * H * Q40 + 8 * d_in + 4 * 8 * H
    assert 5 * nbytes == pytest.approx(one(DENSE) + 4 * one(WIDTH))
    assert 5 * ops == pytest.approx(2.0 * 8 * H * (DENSE + 4 * WIDTH))


@pytest.mark.parametrize("d_out,d_in,d_held", [(4096, H, 2 * WIDTH), (6144, WIDTH, H)])
def test_a_grouped_launch_reads_the_experts_its_steps_tokens_touch(d_out, d_in, d_held):
    touched = counts.experts_touched(16, 256, 8, 8)
    nbytes, ops = counts.kernel_launch(CONFIG, "held_experts_t8", [16, 8, d_out])
    weights = touched * d_in * d_held * Q40
    assert weights / nbytes > 0.9 and ops == pytest.approx(2 * touched * 8 * d_in * d_held)
    # a prompt piece's bucket of 32 rows: 256 tokens touch every one of the 16
    chunk, _ = counts.kernel_launch(CONFIG, "held_experts_t256", [16, 32, d_out])
    assert 16 / touched < chunk / nbytes < 1.5 * 16 / touched


@pytest.mark.parametrize("role,shape", [("held_experts", [16, 8, 4096]), ("lin_in", [8, 4096]),
                                        ("down", [8, 4096]), ("wqkv", [8, 6144])])
def test_a_launch_the_configuration_does_not_make_is_an_error(role, shape):
    with pytest.raises(ValueError):
        counts.kernel_launch(CONFIG, role, shape)


def test_the_counts_know_every_key_of_the_configurations_file_and_the_catalogs():
    assert set(CONFIG) - families.HARNESS_KEYS <= counts.CONFIG_KEYS
    # every number of the published config stands in the file under its key, but for the cut
    published = {"hidden_size": 6144, "intermediate_size": 12288, "moe_intermediate_size": 2048,
                 "num_attention_heads": 64, "num_key_value_heads": 64, "q_lora_rank": 2048, "kv_lora_rank": 512,
                 "qk_head_dim": 256, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
                 "head_dim": 64, "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
                 "num_experts_per_tok": 8, "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
                 "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-05, "max_position_embeddings": 202752,
                 "num_nextn_predict_layers": 1, "moe_layer_freq": 1, "ep_size": 1}
    assert {k: CONFIG[k] for k in published} == published
    assert set(CONFIG["reduced"]) == set(CONFIG["reduced_from"])
