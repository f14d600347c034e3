"""Operations and bytes of a sparse-expert decoder with window and
global layers, computed from its sizes: the yardstick for ``mfu_pct`` in
the ``moe_lm`` family's cells and for the roofline shares of the
kernels that family brought. Kept here, beside ``flops.py``, so that no
change to the program can move it.

Conventions as in ``flops.py`` (one multiply-accumulate is two
operations, training is three times the forward pass, recomputation and
elementwise work are not counted), with one difference from the dense
decoder's: **what the masks leave is what is counted.** Attention's
scores and values are counted over the keys a query really sees, the
mean of which is ``(s + 1) / 2`` in a global causal layer and ``(w(w +
1)/2 + (s - w) w) / s`` in a layer with a window ``w < s``. The dense
family's unhalved ``4sd`` would count 16% more than this model
computes at the cell's sizes. The experts are counted for the pairs
(token, expert) whose expert is held, under uniform routing:
``experts_per_token * held / experts`` experts a token.
"""

#: forward + backward, as a multiple of the forward pass (flops.py's)
TRAIN_FLOP_MULT = 3.0


def mean_visible_keys(s: int, window=None) -> float:
    """Mean over the ``s`` queries of a causal layer of the keys each
    sees (itself included), with a sliding ``window`` or without."""
    if window is None or window >= s:
        return (s + 1) / 2.0
    return (window * (window + 1) / 2.0 + (s - window) * window) / s


def fwd_flops_per_token(sizes: dict, sequence: int) -> float:
    """Matmul operations per token of one forward pass, from a
    configuration's ``sizes`` (chipbench/configs/smallthinker-21b-a3b.json
    names them) at ``sequence`` positions."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    projections = 2 * d * (heads + 2 * kv) * hd + 2 * heads * hd * d
    router = 2 * d * sizes["moe_num_primary_experts"]
    experts_a_token = (sizes["moe_num_active_primary_experts"]
                       * sizes["experts_held"]
                       / sizes["moe_num_primary_experts"])
    experts = experts_a_token * 3 * 2 * d * sizes["moe_ffn_hidden_size"]
    total = 0.0
    for i in range(sizes["n_layer"]):
        window = (sizes["sliding_window_size"]
                  if sizes["sliding_window_layout"][i] else None)
        attention = 4 * heads * hd * mean_visible_keys(sequence, window)
        total += projections + router + experts + attention
    return total + 2 * d * sizes["embedding_rows"]


# -- the fused attention kernels, by the tiles they compute ---------------

#: matmuls of [block_q, block_k, head] a kernel makes per score tile it
#: computes: the forward ``q k^T`` and ``p v``; the dQ kernel ``q k^T``,
#: ``do v^T`` and ``ds k``; the dK/dV kernel those two and ``p^T do``,
#: ``ds^T q``
MATMULS_PER_TILE = {"hvd_flash_fwd": 2, "hvd_flash_bwd_dq": 3,
                    "hvd_flash_bwd_dkv": 4}


def flash_tiles(s: int, block_q: int, block_k: int, window=None) -> int:
    """Score tiles one head's causal self-attention over ``s`` positions
    computes at these blocks: those the diagonal or the window's far
    edge cuts count whole, those either empties do not count."""
    tiles = 0
    for qi in range(s // block_q):
        for ki in range(s // block_k):
            first_row, last_row = qi * block_q, (qi + 1) * block_q - 1
            first_col, last_col = ki * block_k, (ki + 1) * block_k - 1
            seen = first_col <= last_row
            if window is not None:
                seen = seen and first_row - last_col < window
            tiles += seen
    return tiles


def flash_kernel_flops(kernel: str, batch: int, heads: int, s: int,
                       head_dim: int, block_q: int, block_k: int,
                       window=None) -> float:
    """Operations of one call of a fused attention kernel (``kernel``:
    its name without the window's suffix)."""
    return (MATMULS_PER_TILE[kernel] * 2.0 * block_q * block_k * head_dim
            * flash_tiles(s, block_q, block_k, window) * batch * heads)


# -- the grouped matmuls of the expert layer ------------------------------

def grouped_matmul_seconds(rows: float, contract: int, out: int, groups: int,
                           itemsize: int, peak_flops: float,
                           peak_bytes: float):
    """The least time a chip could take for one grouped matmul over
    ``rows`` routed rows, [rows, contract] x [groups, contract, out] (a
    weight gradient moves the same three arrays, the weights as its
    result): the larger of operations over peak and bytes over peak,
    every operand and the result moved once. → (seconds, which bound)."""
    flop = 2.0 * rows * contract * out
    moved = itemsize * (rows * contract + rows * out
                        + groups * contract * out)
    by_compute, by_memory = flop / peak_flops, moved / peak_bytes
    return max(by_compute, by_memory), ("compute" if by_compute >= by_memory
                                        else "memory")
