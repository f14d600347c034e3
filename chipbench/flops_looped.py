"""Operations of a looped decoder (one stack of blocks run several times
over its own output, an exit after every pass), computed from its sizes:
the yardstick for ``mfu_pct`` in the ``looped_lm`` family's cells. Kept
here, beside ``flops.py`` and ``flops_moe.py``, so that no change to the
program can move it.

Conventions as in ``flops_moe.py`` (one multiply-accumulate is two
operations, training is three times the forward pass, norms, rotary
turns, softmax, recomputation and elementwise work are not counted,
**what the mask leaves is what is counted**: scores and values over the
``(s + 1) / 2`` keys a causal query sees on average). **Every
application counts**: a block that runs ``total_ut_steps`` times a step
is counted as often, though its weights exist once; and **every exit
counts**: each pass ends in the classifier over the whole vocabulary and
the gate's one dot product a token.
"""

from chipbench.flops_moe import TRAIN_FLOP_MULT, mean_visible_keys

__all__ = ["TRAIN_FLOP_MULT", "block_fwd_flops_per_token",
           "exit_fwd_flops_per_token", "fwd_flops_per_token"]


def block_fwd_flops_per_token(sizes: dict, sequence: int) -> float:
    """One application of one block: the four projections, the gated
    feed-forward's three matmuls, and what the causal mask leaves of the
    scores and the values."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    projections = 2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d
    mlp = 6 * d * sizes["intermediate_size"]
    attention = 4 * heads * hd * mean_visible_keys(sequence)
    return projections + mlp + attention


def exit_fwd_flops_per_token(sizes: dict) -> float:
    """One exit: the classifier over every row and the gate."""
    return 2 * sizes["hidden_size"] * sizes["vocab_size"] \
        + 2 * sizes["hidden_size"]


def fwd_flops_per_token(sizes: dict, sequence: int) -> float:
    """Matmul operations per token of one forward pass of the training
    loss, from a configuration's ``sizes``
    (chipbench/configs/ouro-2.6b.json names them) at ``sequence``
    positions: ``total_ut_steps`` passes over ``n_layer`` blocks, an exit
    after each."""
    passes = sizes["total_ut_steps"]
    return passes * (sizes["n_layer"]
                     * block_fwd_flops_per_token(sizes, sequence)
                     + exit_fwd_flops_per_token(sizes))
