"""Window executor: share of the roofline the Pallas window kernel
reaches, in %.

Least time of each window run while traced: the larger of its FLOPs
over the chip's peak and its bytes over HBM bandwidth (`counts`);
fp32 windows are held to the published bf16 peak. Decode windows
(M = 128, K >= 2048) are bound by bytes. The kernel time is the summed
device time of the trace's operations named for the kernel. Nothing is
returned where the trace holds no such operation or the windows
counted from the jobs' progress disagree with the server's count.
"""
import counts


def read(ctx):
    if ctx.trace is None or not ctx.traced_windows:
        return None
    if len(ctx.traced_windows) != ctx.traced_windows_counted:
        return None
    t_kernel = sum(
        s for n, s in ctx.trace["op_s"].items() if ctx.kernel in n
    )
    if t_kernel <= 0:
        return None
    peak, bw = ctx.peaks["flops_per_s"], ctx.peaks["hbm_bytes_per_s"]
    t_min = sum(
        max(counts.window_flops(K, tiles, ctx.block) / peak,
            counts.window_bytes(M, K, N, ctx.block, start, tiles) / bw)
        for M, K, N, start, tiles in ctx.traced_windows
    )
    return 100.0 * t_min / t_kernel
