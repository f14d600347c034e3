"""Operations a model needs, computed from its sizes. The yardstick for
``mfu_pct`` and ``busy_mfu_pct``: kept here so that no change to the
program can move it.

Conventions (README.md, "FLOP conventions"): one multiply-accumulate is
two operations; a training step needs three times the forward pass
(forward + the two backward matmuls per forward matmul); operations that
rematerialisation repeats are not counted; elementwise work, norms and
softmax are not counted.
"""

#: forward + backward, as a multiple of the forward pass
TRAIN_FLOP_MULT = 3.0

def resnet_fwd_flops_per_image(stage_sizes, num_filters: int,
                               image_size: int, num_classes: int) -> float:
    """Forward operations per image of a bottleneck ResNet v1.5, counted
    layer by layer from its sizes (convolutions and the classifier).
    ResNet-50 at 224x224 and 1000 classes gives 8.178e9, which is
    bench.py's RESNET50_FWD_FLOP_PER_IMG (2 x 4.09 G multiply-accumulates;
    He et al., arXiv:1512.03385, Table 1 gives 3.8e9 for v1, and v1.5's
    stride on the 3x3 adds the rest)."""
    def conv(h, k, cin, cout, stride):
        out = -(-h // stride)
        return out, 2.0 * out * out * k * k * cin * cout

    total = 0.0
    h, f = conv(image_size, 7, 3, num_filters, 2)
    total += f
    h = -(-h // 2)  # 3x3/2 max pool
    cin = num_filters
    for i, blocks in enumerate(stage_sizes):
        width = num_filters * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            _, f1 = conv(h, 1, cin, width, 1)
            h2, f2 = conv(h, 3, width, width, stride)
            _, f3 = conv(h2, 1, width, 4 * width, 1)
            total += f1 + f2 + f3
            if cin != 4 * width or stride != 1:
                total += conv(h, 1, cin, 4 * width, stride)[1]
            h, cin = h2, 4 * width
    return total + 2.0 * cin * num_classes


def lm_fwd_flops_per_token(n_layers: int, d_model: int, d_ff: int,
                           vocab_size: int, seq: int) -> float:
    """Matmul operations per token of one forward pass of a dense
    decoder: QKV and output projections (8 d^2), the feed-forward pair
    (4 d f), attention scores and values over the full ``seq`` context
    (4 s d: the PaLM appendix-B convention, which does not halve for the
    causal mask), and the tied classifier (2 d V). Copied from
    benchmarks/bench_transformer.py fwd_flops_per_token."""
    per_block = 8 * d_model * d_model + 4 * d_model * d_ff + 4 * seq * d_model
    return float(n_layers * per_block + 2 * d_model * vocab_size)
