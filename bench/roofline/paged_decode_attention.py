"""Work that paged decode attention needs: for each decode row with n
tokens of context, in each layer, 4 * H * D * n FLOPs (scores and
values) and the bf16 K and V of those n tokens (2 * n * Hkv * D * 2
bytes) plus the row's query and output (2 * H * D * 2 bytes).  Only the
valid tokens of live rows count, whatever the kernel reads."""

# the Pallas kernel's custom call, as the device trace names it
TRACE_NAME = r"^paged_decode_attention(\.|$)"


def work(ctx):
    z = ctx.dims
    H, Hkv, D, L = z["H"], z["Hkv"], z["D"], z["L"]
    flops = nbytes = 0
    for s in ctx.steps:
        for n in s.decode_ctx:
            flops += 4 * H * D * n
            nbytes += 2 * n * Hkv * D * 2 + 2 * H * D * 2
    return L * flops, L * nbytes
