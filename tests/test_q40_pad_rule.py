"""The rule that pads a Q40 pack's output columns (``ops.q40._d_padded``): a
width over 1024 takes the least multiple of 128 that costs no more output
tiles than the next multiple of 1024 at any row class served with a tile of
1024 or more. Held here over every Q40 leaf of the benchmark's eight
configurations as the loader builds them (shapes only: tests/q40_leaf_shapes.py)
and over a table of widths; no kernel runs.
"""

import pytest

from distributed_llama_tpu.ops import q40
from tests import q40_leaf_shapes as leaf_shapes

# the tiles' caps of the row classes up to 32, 64 and 256 rows
CAPS = (4096, 2048, 1024)


def next_1024(d: int) -> int:
    return -(-d // 1024) * 1024


def tiles(width: int) -> tuple[int, ...]:
    """Output tiles (grid steps along the columns) of a pack ``width`` wide at
    each cap, by the function the dispatch takes its tile from."""
    return tuple(width // q40._largest_divisor_tile(width, cap, 128) for cap in CAPS)


def test_the_caps_are_the_dispatch_tables():
    assert tuple(cap for _, cap in q40._BLOCK_D_BY_ROWS if cap >= 1024) == CAPS
    assert q40.BLOCK_D == CAPS[0]


# width, padded as before this rule, tiles there, padded by the rule, tiles there
WIDTHS = [
    (1152, 2048, (1, 1, 2), 1280, (1, 1, 2)),  # 1152 itself: three tiles of 384 at 256 rows
    (1280, 2048, (1, 1, 2), 1280, (1, 1, 2)),  # two tiles of 640
    (1344, 2048, (1, 1, 2), 1536, (1, 1, 2)),  # GLM's q_a|kv_a; 1408 is eleven tiles of 128
    (1536, 2048, (1, 1, 2), 1536, (1, 1, 2)),  # Granite-Small's expert gate|up: two of 768
    (1792, 2048, (1, 1, 2), 1792, (1, 1, 2)),  # two of 896
    (1920, 2048, (1, 1, 2), 2048, (1, 1, 2)),  # 1920: three of 640
    (2304, 3072, (1, 2, 3), 2304, (1, 2, 3)),  # two of 1152, three of 768
    (2560, 3072, (1, 2, 3), 3072, (1, 2, 3)),  # Solar's gate|up: 2560 is a fourth tile of 640
    (8512, 9216, (3, 6, 9), 9216, (3, 6, 9)),  # Granite-Micro's ssm_in
    (11008, 11264, (4, 8, 11), 11264, (4, 8, 11)),  # Llama-2-7B's down as an output
    (16768, 17408, (8, 17, 17), 17408, (8, 17, 17)),  # Granite-Small's ssm_in
    (22016, 22528, (8, 11, 22), 22528, (8, 11, 22)),  # EvaByte's gate|up: 22016 is 43 tiles
    (154880, 155648, (38, 76, 152), 155648, (38, 76, 152)),  # GLM's head
]


@pytest.mark.parametrize("width,before,tiles_before,padded,tiles_now", WIDTHS, ids=[str(w[0]) for w in WIDTHS])
def test_a_widths_padding_and_tiles(width, before, tiles_before, padded, tiles_now):
    assert (next_1024(width), tiles(before)) == (before, tiles_before)
    assert (q40._d_padded(width), tiles(padded)) == (padded, tiles_now)
    assert width <= padded <= before and padded % 128 == 0
    # the least such width: every narrower multiple of 128 costs a tile somewhere
    for narrower in range(-(-width // 128) * 128, padded, 128):
        assert any(a > b for a, b in zip(tiles(narrower), tiles_before)), narrower


def test_widths_up_to_1024_are_not_padded():
    assert [q40._d_padded(d) for d in (1, 96, 320, 1000, 1024)] == [1, 96, 320, 1000, 1024]


def test_no_width_takes_more_tiles_or_more_columns_than_before():
    """Every width to 40000 columns: never wider than the next multiple of
    1024, never a tile more at any cap; the rule moves four stretches."""
    moved = []
    for d in range(1025, 40001):
        padded, before = q40._d_padded(d), next_1024(d)
        assert d <= padded <= before and padded % 128 == 0, d
        if padded != before:
            assert all(a <= b for a, b in zip(tiles(padded), tiles(before))), d
            moved.append(d)
    edges = [d for d in moved if d - 1 not in moved or d + 1 not in moved]
    assert edges == [1025, 1792, 2049, 2304, 3073, 3584, 5121, 5376]


# The cells' packs the rule moved when it came (PR 51), of the eight configurations the benchmark
# then had: {configuration: {leaf: (width, padded before, padded now)}}. A configuration that came
# later has no "before": it is held to the tiles alone.
MOVED = {
    "evabyte-6.5b-q40-16l": {},
    "glm-4.7-flash-q40-stage0": {"qkv_a": (1344, 2048, 1536)},
    "granite-4.0-h-micro-q40": {},
    "granite-4.0-h-small-q40-10l-ep4": {"experts_gate_up": (1536, 2048, 1536)},
    "k-exaone-236b-q40-8l-ep8": {},
    "mistral-7b-q40-16l": {},
    "mixtral-8x7b-q40-4l": {},
    "solar-open2-250b-q40-8l-ep16": {},
}


def test_the_eight_configurations_are_all_there():
    assert set(MOVED) <= set(leaf_shapes.CONFIGS)


@pytest.mark.parametrize("name", leaf_shapes.CONFIGS)
def test_a_configurations_packs_take_no_more_tiles_and_only_the_named_ones_move(name):
    """Every Q40 leaf of the configuration at its published widths, as the
    loader fuses, stacks and cuts it: the rule's width is the pack's, costs no
    tile more than the next multiple of 1024, and differs from it for the
    leaves of ``MOVED`` alone."""
    leaves = leaf_shapes.q40_leaves(leaf_shapes.param_shapes(name))
    assert leaves, name
    moved = {}
    for leaf, packs in leaves.items():
        for pack in packs:
            width = pack.d
            assert pack.d_padded == q40._d_padded(width), (leaf, width)
            if width <= 1024:  # not padded, before or now (EvaByte's head of 320 takes no tile at all)
                assert pack.d_padded == width, (leaf, width)
                continue
            before = next_1024(width)
            assert all(a <= b for a, b in zip(tiles(pack.d_padded), tiles(before))), (leaf, width)
            if pack.d_padded != before:
                moved[leaf] = (width, before, pack.d_padded)
    if name in MOVED:
        assert moved == MOVED[name], f"{name}: the rule moves {moved}; ISSUE 51's table names {MOVED[name]}"
