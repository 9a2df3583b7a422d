"""Each fault the hybrid cell can have comes out NOT correct through the
``hybrid_serve`` driver at a tiny size on the CPU, held to the real cell's
limit: the prefill's padding folded into the recurrent state, the
convolution's tail taken from padded positions, one altered token. (The int8
control has a file of its own, at a width where the limit means what it means
on the chip.) ``benchmarks/hybrid_faults.py`` plants the same faults on the
chip at the cell's own size."""

import numpy as np
import pytest
from benchmark_testlib import cpu_device
from hybrid_testlib import CELL, hybrid_root

from benchmarks import harness, hybrid_faults

SEED = 2**31 + 13


def gap(result) -> dict:
    return result["compared"]["token_logit_gap_mean"]


@pytest.mark.parametrize("fault", ["pad_in_state", "pad_in_tail"])
def test_padding_that_reaches_the_recurrence_is_not_correct(tmp_path, fault):
    with hybrid_faults.planted(fault):
        result = harness.run_cell(CELL, SEED, 0.6, False, root=hybrid_root(tmp_path),
                                  devices=cpu_device())
    assert result["correct"] is False and result["failed"] == 0
    assert gap(result)["value"] > 2 * gap(result)["limit"]
    assert result["compared"]["wrong_length_requests"]["value"] == 0


def test_the_planted_fault_is_gone_afterwards(tmp_path):
    """``planted`` drops the compiled programs on both sides: the run after a
    fault is sound again (and the one inside it was not the cached sound one)."""
    with hybrid_faults.planted("pad_in_state"):
        pass
    result = harness.run_cell(CELL, SEED, 0.6, False, root=hybrid_root(tmp_path),
                              devices=cpu_device())
    assert result["correct"] is True


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path, monkeypatch):
    from distributed_ml_pytorch_tpu.serving.cache import SlotKVPool

    real = SlotKVPool.decode_block_step

    def altered(self, *args):
        toks = np.array(real(self, *args))
        toks[:, 1] = (toks[:, 1] + 17) % 120
        return toks

    monkeypatch.setattr(SlotKVPool, "decode_block_step", altered)
    result = harness.run_cell(CELL, SEED, 0.6, False, root=hybrid_root(tmp_path),
                              devices=cpu_device())
    assert result["correct"] is False and gap(result)["value"] > gap(result)["limit"]


def test_the_state_rounded_to_bfloat16_can_be_planted():
    """``bf16_state`` is a reading, not a fault with a verdict: the test only
    holds that the patch rounds the state the rule returns."""
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.ops import gated_delta

    x = [jnp.full(s, 0.3) for s in ((1, 2, 8), (1, 2, 8), (1, 2, 16), (1, 2), (1, 2))]
    state = jnp.full((1, 2, 16, 8), 1.001)
    _, sound = gated_delta.gated_delta_step(*x, state)
    with hybrid_faults.planted("bf16_state"):
        _, low = gated_delta.gated_delta_step(*x, state)
    assert low.dtype == jnp.float32
    assert bool((low == low.astype(jnp.bfloat16).astype(jnp.float32)).all())
    assert not bool((sound == sound.astype(jnp.bfloat16).astype(jnp.float32)).all())
