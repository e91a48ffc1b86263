"""Model FLOPs of the tokens the window's steps processed, for the
whole-step share of the chip's peak (mfu).

Per token: 2 FLOPs per weight of every layer's projections and MLP, and
4 * H * D per key it attends to in each layer.  The output table (2 * d
* V) counts once per generated token and once per prompt, at its last
token; padding, empty slots and recompute do not count.  Bytes are not
counted (returned as 0)."""


def work(ctx):
    z = ctx.dims
    d, H, Hkv, D, ff, L, V = (z["d"], z["H"], z["Hkv"], z["D"], z["ff"],
                              z["L"], z["V"])
    per_layer = d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * ff
    head = 2 * d * V
    flops = 0
    for s in ctx.steps:
        for n in s.decode_ctx:
            flops += 2 * per_layer * L + 4 * H * D * n * L + head
        for o, T, last in s.chunks:
            flops += 2 * per_layer * L * T
            flops += 4 * H * D * (o * T + T * (T + 1) // 2) * L
            flops += head if last else 0
    return flops, 0
