"""The least work a step needs, from shapes alone: operations and bytes the
algorithm requires, not what a compiler emits. Recomputation, padding, layout
copies and dense gathers count for nothing here, so a share of the peak
computed from these can only read under 100 %."""

RESNET50_BLOCKS = (3, 4, 6, 3)


def conv_macs(h_out, w_out, k, cin, cout):
    """Multiply-accumulates of one k x k convolution per image."""
    return h_out * w_out * k * k * cin * cout


def resnet50_convs(image=224, classes=1000):
    """[(name, MACs per image)] of the forward pass: every convolution and
    the classifier (BatchNorm, ReLU, pooling and adds are not counted)."""
    out = []
    size = image // 2                                   # conv1, stride 2
    out.append(("conv1", conv_macs(size, size, 7, 3, 64)))
    size //= 2                                          # max-pool, stride 2
    cin = 64
    for s, n in enumerate(RESNET50_BLOCKS):
        width = 64 * 2 ** s
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            p = f"l{s}.b{b}"
            out.append((p + ".c1", conv_macs(size, size, 1, cin, width)))
            size_out = size // stride                   # stride on the 3x3
            out.append((p + ".c2", conv_macs(size_out, size_out, 3, width,
                                             width)))
            out.append((p + ".c3", conv_macs(size_out, size_out, 1, width,
                                             width * 4)))
            if b == 0:
                out.append((p + ".sc", conv_macs(size_out, size_out, 1, cin,
                                                 width * 4)))
            size, cin = size_out, width * 4
    out.append(("fc", cin * classes))
    return out


def resnet50_train_step_flops(batch, image=224, classes=1000):
    """Forward plus backward of one step: 2 operations a MAC; backward costs
    one pass for the gradient of the input and one for that of the weights,
    and the first convolution needs no gradient of its input."""
    macs = dict(resnet50_convs(image, classes))
    total = sum(3 * m for m in macs.values()) - macs["conv1"]
    return 2 * total * batch


def gpt2_matmul_params(embed, layers, vocab, mlp_ratio=4):
    """Weights every decoded token multiplies: the blocks and the logits."""
    block = embed * 3 * embed + embed * embed + 2 * mlp_ratio * embed * embed
    return layers * block + vocab * embed


def gpt2_weight_bytes(embed, layers, vocab, mlp_ratio=4, itemsize=2):
    """Bytes a decode step must read of the weights: every matmul weight and
    bias once, whatever the batch (position rows and LayerNorm are noise)."""
    biases = layers * (3 * embed + embed + mlp_ratio * embed + embed)
    return (gpt2_matmul_params(embed, layers, vocab, mlp_ratio)
            + biases) * itemsize


def kv_bytes_per_token(embed, layers, itemsize=2):
    return layers * 2 * embed * itemsize


def gpt2_decode_step(embed, layers, vocab, live_rows, live_tokens,
                     itemsize=2):
    """One decode step over ``live_rows`` requests holding ``live_tokens``
    cached tokens in all: (operations, bytes) it needs at the least."""
    flops = (2 * gpt2_matmul_params(embed, layers, vocab) * live_rows
             + 4 * embed * layers * live_tokens)       # QK^T and PV
    data = (gpt2_weight_bytes(embed, layers, vocab, itemsize=itemsize)
            + kv_bytes_per_token(embed, layers, itemsize) * live_tokens)
    return flops, data


def least_seconds(flops, data, peaks):
    """Roofline: the larger of operations over the compute peak and bytes
    over the memory peak, and which one binds."""
    by_compute = flops / peaks["flops_per_s"]
    by_memory = data / peaks["hbm_bytes_per_s"]
    return max(by_compute, by_memory), ("compute" if by_compute > by_memory
                                        else "memory")
