"""Analytic operation counts, from shapes alone.  The yardstick's own
arithmetic: nothing here reads the program.  A multiply-add is 2 FLOPs.
Training costs 3x the forward pass (forward, gradient with respect to the
activations, gradient with respect to the weights); recomputation is not
counted."""

TRAIN_OVER_FORWARD = 3.0


def conv_flops(h_out, w_out, k, cin, cout):
    return 2.0 * h_out * w_out * k * k * cin * cout


def resnet50_forward_flops(image_size=224, classes=1000):
    """ResNet-50 v1 (He et al. 2015, table 1), stride on the 3x3 of the
    first bottleneck of a stage (as torchvision and this repo place it).
    Convolutions and the classifier only: batch-norm, ReLU and pooling are
    under 1% and are left out.  The 7x7/2 stem is counted as published (its
    space-to-depth rewrite multiplies by a few structural zeros more)."""
    s = image_size // 2                       # 112 after the stem
    total = conv_flops(s, s, 7, 3, 64)
    s //= 2                                   # 56 after the max-pool
    cin = 64
    for stage, (width, blocks) in enumerate(
            [(64, 3), (128, 4), (256, 6), (512, 3)]):
        for b in range(blocks):
            stride = 2 if (stage > 0 and b == 0) else 1
            s_out = s // stride
            total += conv_flops(s, s, 1, cin, width)
            total += conv_flops(s_out, s_out, 3, width, width)
            total += conv_flops(s_out, s_out, 1, width, 4 * width)
            if stride != 1 or cin != 4 * width:
                total += conv_flops(s_out, s_out, 1, cin, 4 * width)
            cin, s = 4 * width, s_out
    return total + 2.0 * cin * classes


def transformer_lm_forward_flops(seq, layers, d, ffn, vocab):
    """One sequence of ``seq`` tokens through a decoder-only transformer
    with a tied output head: projections, causal attention (half of the
    seq x seq products are masked out and not counted), FFN, logits."""
    per_layer = (2.0 * seq * d * d * 4            # q, k, v, out
                 + 2.0 * seq * seq * d            # qk^T and pv, causal half
                 + 2.0 * seq * d * ffn * 2)       # two FFN matmuls
    return layers * per_layer + 2.0 * seq * d * vocab
