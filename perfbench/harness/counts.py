"""Operations and bytes an algorithm needs, from its shapes alone.

These are the yardstick for ``*_roofline`` and ``*mfu*`` metrics: what
the forward and backward passes require, never what a program happens to
execute (recomputation, padding and masked-out work do not count).
"""


def gpt2_param_counts(cfg: dict) -> dict:
    """Parameter counts of the repo's GPT-2 block (no projection biases):
    ``matmul`` are the weights a token multiplies through (the tied head
    once, the embedding lookup not at all)."""
    d, L, V, S = (cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"],
                  cfg["n_positions"])
    inner = cfg["n_inner"]
    per_layer = 4 * d * d + 2 * d * inner
    norms = L * 4 * d + 2 * d
    return {"matmul": L * per_layer + V * d,
            "total": L * per_layer + V * d + S * d + norms,
            "norms": norms}


def gpt2_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward FLOPs one token needs at sequence length ``seq_len``:
    two per matmul weight, and causal attention over the mean context
    ``(seq_len + 1) / 2`` (scores and values: 2 x 2 x context x width a
    layer). The masked-out half is not needed, so it is not counted."""
    d, L = cfg["n_embd"], cfg["n_layer"]
    context = (seq_len + 1) / 2.0
    return 2.0 * gpt2_param_counts(cfg)["matmul"] + L * 4.0 * context * d


def gpt2_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward plus backward (twice the forward); no recomputation."""
    return 3.0 * gpt2_forward_flops_per_token(cfg, seq_len)


def gpt2_decode_bytes(cfg: dict, live_context_tokens: float,
                      weight_itemsize: int = 2,
                      kv_itemsize: int = 2) -> float:
    """Bytes one decode step has to read: every matmul weight and norm
    once, in the type the matmuls consume (bf16 unless said otherwise:
    the fewest bytes any serving of this configuration could read), and
    the K and V of the live tokens of all lanes."""
    c = gpt2_param_counts(cfg)
    kv_per_token = 2 * cfg["n_layer"] * cfg["n_embd"] * kv_itemsize
    return ((c["matmul"] + c["norms"]) * weight_itemsize
            + live_context_tokens * kv_per_token)


def resnet50_layers(image_size: int = 224, num_classes: int = 1000):
    """Every convolution and the classifier of ResNet-50 v1.5 (stride in
    the 3x3) as ``(name, out_h, out_w, k, c_in, c_out)``."""
    layers = []
    h = image_size // 2
    layers.append(("conv_init", h, h, 7, 3, 64))
    h //= 2                                           # 3x3/2 max pool
    c_in = 64
    for stage, blocks in enumerate((3, 4, 6, 3)):
        f = 64 * 2 ** stage
        for b in range(blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            layers.append((f"s{stage}b{b}.conv1", h, h, 1, c_in, f))
            h_out = h // stride
            layers.append((f"s{stage}b{b}.conv2", h_out, h_out, 3, f, f))
            layers.append((f"s{stage}b{b}.conv3", h_out, h_out, 1, f, 4 * f))
            if b == 0:
                layers.append((f"s{stage}b{b}.proj", h_out, h_out, 1, c_in,
                               4 * f))
            h, c_in = h_out, 4 * f
    layers.append(("fc", 1, 1, 1, c_in, num_classes))
    return layers


def resnet50_forward_flops_per_image(image_size: int = 224,
                                     num_classes: int = 1000) -> float:
    """Two FLOPs a multiply-accumulate over every convolution and the
    classifier; norms, activations and pooling are not counted."""
    return float(sum(2 * oh * ow * k * k * ci * co
                     for _, oh, ow, k, ci, co in
                     resnet50_layers(image_size, num_classes)))


def resnet50_train_flops_per_image(image_size: int = 224,
                                   num_classes: int = 1000) -> float:
    return 3.0 * resnet50_forward_flops_per_image(image_size, num_classes)
