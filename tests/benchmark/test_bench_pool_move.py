"""``kv_pool_move_share`` (PR 25): the share of the device's busy time that
operations outside the kernels spend producing a whole KV pool. On hand-made
operation tables, on the operation tables of traced tails recorded on a TPU v5
lite before and after the stacked pools moved into the layer scan's carry (my
chip runs, PR 25), and on the small recorded trace."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import xplane  # noqa: E402
from benchmark.layer_metrics import kv_pool_move_share  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


def ctx(ops, busy_s, hkv=8, pages=1750, page=16):
    return {
        "cfg": {"num_key_value_heads": hkv},
        "after": {"kv_pool": {"num_pages": pages, "page_size": page}},
        "trace": {"devices": 1, "busy_s": busy_s, "ops": ops},
    }


# short names as `xplane.short_name` cuts them (64 characters): the ledger's
# for PR 24, `mistral7b.decode_batch`
PARENT_OPS = [
    ("ragged_paged_attention.5_custom-call_bf16_352_8_4_128", 0.408, 900),
    ("fusion.226_fusion_bf16_352_14336", 0.188, 900),
    ("copy.171_copy_bf16_32_8_1750_16_128", 0.146, 28),
    ("copy_bitcast_fusion.6_fusion_bf16_8_1750_16_128", 0.090, 900),
    ("bitcast_dynamic-update-slice_fusion.4_fusion_bf16_32_8_1750_16_1", 0.085, 900),
    # a kernel whose operand list names the pool is still a kernel
    ("paged_attention_decode.6_custom-call_bf16_32_8_1750_16_128", 0.100, 900),
]


def test_whole_pool_operations_outside_the_kernels_are_counted():
    got = kv_pool_move_share.read(ctx(PARENT_OPS, busy_s=2.0))
    assert got == pytest.approx(100.0 * (0.146 + 0.090 + 0.085) / 2.0)


def test_the_match_is_on_the_middle_of_the_shape():
    # cut at 64 characters the name ends in `_16_1`, not `_16_128`
    name = PARENT_OPS[4][0]
    assert len(name) == 64 and not name.endswith("_128")
    assert kv_pool_move_share.read(ctx([PARENT_OPS[4]], busy_s=0.085)) == \
        pytest.approx(100.0)


def test_another_pool_is_not_this_cells():
    # Mixtral's pool (8225 pages) in a cell whose pool has 1750
    ops = [("copy.188.remat2_copy_bf16_8_8225_16_128", 0.2, 6)]
    assert kv_pool_move_share.read(ctx(ops, busy_s=1.0)) == 0.0
    assert kv_pool_move_share.read(ctx(ops, busy_s=1.0, pages=8225)) == \
        pytest.approx(20.0)


@pytest.mark.parametrize("drop", ["cfg", "after", "trace"])
def test_nothing_to_read_gives_nothing(drop):
    c = ctx(PARENT_OPS, busy_s=2.0)
    c[drop] = {} if drop != "trace" else None
    assert kv_pool_move_share.read(c) is None


@pytest.mark.parametrize("side,low,high", [
    ("parent", 54.0, 55.5), ("change", 0.0, 0.0),
])
def test_the_recorded_tails_of_prefill_batch(side, low, high):
    """The 40 largest operations of `mixtral8x7b.prefill_batch`'s traced tail
    at PR 24's commit and at PR 25's (my chip runs, PR 25; the whole tables
    read 54.72 and 0.0): six layer-pool slices, updates and layout copies a
    layer and pass before, only the `paged_kv_write` kernel after, whose
    result is the stack and which is a kernel."""
    rec = json.loads((DATA / "ops_prefill_batch_{}.json".format(side)).read_text())
    got = kv_pool_move_share.read(
        ctx([tuple(op) for op in rec["ops"]], rec["busy_s"], pages=8225))
    assert low <= got <= high
    names = [op[0] for op in rec["ops"]]
    assert any(n.startswith("paged_kv_write") for n in names) is (side == "change")


def test_the_recorded_trace_moves_no_pool():
    red = xplane.reduce_trace(str(DATA / "tpu_small.xplane.pb"))
    c = ctx(red["ops"], red["busy_s"])
    c["trace"] = red
    assert kv_pool_move_share.read(c) == 0.0
