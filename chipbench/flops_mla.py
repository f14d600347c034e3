"""Operations of a latent-attention (MLA) sparse-expert decoder with
shared experts and leading dense layers, computed from its sizes: the
yardstick for ``mfu_pct`` in the ``mla_moe_lm`` family's cells and for
the roofline share of the kernels that family brought. Kept here, beside
``flops.py`` and ``flops_moe.py``, so that no change to the program can
move it.

Conventions as in ``flops_moe.py`` (one multiply-accumulate is two
operations, training is three times the forward pass, norms, rotary
turns, softmax, recomputation and elementwise work are not counted,
**what the mask leaves is what is counted**: scores and values over the
``(s + 1) / 2`` keys a causal query sees on average; the routed experts
for the pairs (token, expert) whose expert is held, under uniform
routing; the head over the rows held). **The published widths are what
is counted, whatever a kernel pads to**: a head's score is ``qk_nope +
qk_rope`` = 192 wide, its value 128.
"""

from chipbench.flops_moe import TRAIN_FLOP_MULT, flash_tiles, \
    mean_visible_keys


def fwd_flops_per_token(sizes: dict, sequence: int) -> float:
    """Matmul operations per token of one forward pass, from a
    configuration's ``sizes`` (chipbench/configs/kanana-2-30b-a3b.json
    names them) at ``sequence`` positions."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    value, latent = sizes["v_head_dim"], sizes["kv_lora_rank"]
    projections = 2 * (d * heads * (nope + rope)         # W_q
                       + d * (latent + rope)             # W_kva
                       + latent * heads * (nope + value)  # W_kvb
                       + heads * value * d)              # W_o
    attention = (2 * heads * (nope + rope + value)
                 * mean_visible_keys(sequence))
    dense = 6 * d * sizes["intermediate_size"]
    inner = sizes["moe_intermediate_size"]
    experts_a_token = (sizes["num_experts_per_tok"] * sizes["experts_held"]
                       / sizes["n_routed_experts"])
    sparse = (2 * d * sizes["n_routed_experts"]              # router
              + 6 * d * sizes["n_shared_experts"] * inner    # shared
              + experts_a_token * 6 * d * inner)             # routed, held
    leading = min(sizes["first_k_dense_replace"], sizes["n_layer"])
    return (sizes["n_layer"] * (projections + attention) + leading * dense
            + (sizes["n_layer"] - leading) * sparse
            + 2 * d * sizes["embedding_rows"])


# -- the latent attention kernels, by the tiles they compute ---------------

#: matmuls a kernel makes per score tile it computes, by the width each
#: contracts or writes: "qk" the head's whole query/key (``q k^T``, and
#: backward ``ds k`` and ``ds^T q``: the no-rope and the rotary part
#: together), "v" its value (``p v``, ``do v^T``, ``p^T do``)
MATMULS_PER_TILE = {"hvd_mla_fwd": {"qk": 1, "v": 1},
                    "hvd_mla_bwd_dq": {"qk": 2, "v": 1},
                    "hvd_mla_bwd_dkv": {"qk": 2, "v": 2}}


def mla_kernel_flops(kernel: str, batch: int, heads: int, s: int,
                     qk_dim: int, v_dim: int, block_q: int,
                     block_k: int) -> float:
    """Operations of one call of a latent attention kernel over the
    tiles it computes (those the diagonal cuts count whole, those it
    empties not at all: flops_moe.flash_tiles), at the published
    ``qk_dim`` (192) and ``v_dim`` (128)."""
    per_tile = MATMULS_PER_TILE[kernel]
    width = per_tile["qk"] * qk_dim + per_tile["v"] * v_dim
    return (2.0 * block_q * block_k * width
            * flash_tiles(s, block_q, block_k) * batch * heads)
