"""Work that chunked paged prefill attention needs: a chunk of T tokens
at offset o attends causally to o * T + T * (T + 1) / 2 query-key
pairs, each 4 * H * D FLOPs (scores and values), in each layer; it
reads the bf16 K and V of its o + T tokens and its queries, and writes
its output (2 * T * H * D * 2 bytes)."""

# the Pallas kernel's custom call, as the device trace names it
TRACE_NAME = r"^paged_prefill"


def work(ctx):
    z = ctx.dims
    H, Hkv, D, L = z["H"], z["Hkv"], z["D"], z["L"]
    flops = nbytes = 0
    for s in ctx.steps:
        for o, T, _ in s.chunks:
            flops += 4 * H * D * (o * T + T * (T + 1) // 2)
            nbytes += 2 * (o + T) * Hkv * D * 2 + 2 * T * H * D * 2
    return L * flops, L * nbytes
