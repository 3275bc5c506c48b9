"""The operation and byte functions against numbers worked by hand from
the published sizes of both configurations.  They live beside the
reference they count (``benchmarks/reference/gpt2_counts.py``) and the
readers reach them through a cell's reference."""

import os

import pytest

from benchmarks.harness import spec
from benchmarks.reference import gpt2_counts as flops


def sizes(name):
    return spec._json(os.path.join(spec.ROOT, "benchmarks", "configs",
                                   name + ".json"))


# per layer: 4 h^2 (q, k, v, o) + 8 h^2 (the two MLP matrices) = 12 h^2;
# plus the tied head, vocab * h
HAND = {
    "gpt2-medium": {
        "matmul": 24 * 12 * 1024 * 1024 + 50257 * 1024,       # 353,453,056
        # + biases 24 * (4*1024 + 4096 + 1024) + LayerNorms
        # 24 * 4 * 1024 + 2 * 1024 + positions 1024 * 1024
        "total": 24 * 12 * 1024 ** 2 + 50257 * 1024 + 24 * 9216
        + 24 * 4096 + 2048 + 1024 * 1024,                     # 354,823,168
        "kv_bytes_per_token": 24 * 2 * 1024 * 2,
    },
    "gpt2-xl": {
        "matmul": 48 * 12 * 1600 * 1600 + 50257 * 1600,     # 1,554,971,200
        "total": 48 * 12 * 1600 ** 2 + 50257 * 1600 + 48 * 14400
        + 48 * 6400 + 3200 + 1024 * 1600,                   # 1,557,611,200
        "kv_bytes_per_token": 48 * 2 * 1600 * 2,            # 307,200
    },
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_parameter_counts(name):
    s = sizes(name)
    assert flops.matmul_params(s) == HAND[name]["matmul"]
    assert flops.total_params(s) == HAND[name]["total"]
    assert flops.decode_attention_bytes(s, 1) \
        == HAND[name]["kv_bytes_per_token"]
    assert flops.adam_bytes(s) == 28 * HAND[name]["total"]


def test_published_totals():
    """355M and 1.56B as the model cards say (1.5B for XL)."""
    assert round(flops.total_params(sizes("gpt2-medium")) / 1e6) == 355
    assert round(flops.total_params(sizes("gpt2-xl")) / 1e9, 2) == 1.56


@pytest.mark.parametrize("name", sorted(HAND))
def test_attention_and_step_operations(name):
    s = sizes(name)
    h, layers = s["n_embd"], s["n_layer"]
    # 1,024 tokens: 1,024 * 1,025 / 2 = 524,800 (query, key) pairs
    attn = layers * 4 * h * 524_800
    assert flops.attention_flops_causal(s, 1024) == attn
    assert flops.train_flops_per_sequence(s, 1024) \
        == 3 * (2 * HAND[name]["matmul"] * 1024 + attn)
    # the whole sequence in one go equals prefill chunk by chunk plus
    # decode token by token
    whole = flops.forward_flops_at(s, 0, 1024)
    assert whole == 2 * HAND[name]["matmul"] * 1024 + attn
    parts = sum(flops.forward_flops_at(s, a, b) for a, b in
                [(0, 256), (256, 512), (512, 700)]) \
        + sum(flops.forward_flops_at(s, p, p + 1) for p in range(700, 1024))
    assert parts == whole
    assert flops.forward_flops_at(s, 5, 5) == 0


def test_flash_and_medium_per_token_by_hand():
    s = sizes("gpt2-medium")
    ops, nbytes = flops.flash_train_flops_bytes(s, 8, 1024)
    # forward 24 * 4 * 1024 * 524,800 * 8 = 412.7 G; three times with
    # the backward
    assert ops == 3 * 24 * 4 * 1024 * 524_800 * 8
    # 12 tensors of 24 * 8 * 1024 * 1024 bfloat16 elements
    assert nbytes == 12 * 24 * 8 * 1024 * 1024 * 2
    per_token = flops.train_flops_per_sequence(s, 1024) / 1024
    assert 2.27e9 < per_token < 2.28e9        # 6 * 353M + attention


@pytest.mark.parametrize("cell", ["gpt2m-train-1chip", "gpt2m-train-ddp4",
                                  "gpt2xl-chat-open", "gpt2xl-doc-backlog"])
def test_a_cells_reference_carries_the_counts_the_readers_divide_by(cell):
    c = spec.load_cell(cell)
    ref, s = c.reference(), c.config
    for name in ("matmul_params", "total_params", "attention_flops_causal",
                 "forward_flops_at", "train_flops_per_sequence",
                 "flash_train_flops_bytes", "adam_bytes",
                 "decode_attention_bytes"):
        assert callable(getattr(ref, name)), name
    assert ref.total_params(s) == flops.total_params(s)
    assert ref.forward_flops_at(s, 3, 9) == flops.forward_flops_at(s, 3, 9)
    # and what the runners ask of it in place of a model's key names
    assert ref.vocab(s) == 50257 and ref.longest_row(s) == 1024
    assert ref.weight_std(s) == 0.02
