"""Operations and bytes of one window of the tile-window GEMM kernel.

A layer is an ``(M, K) @ (K, N)`` GEMM cut into ``(bm, bn)`` output
tiles; each tile takes the whole K reduction. A window is ``tiles``
consecutive tiles of the row-major tile grid from tile ``start``.

Bytes are what the algorithm has to move for one window, in fp32: the
A rows of the tile rows it touches, the B columns of the tile columns
it touches, and its C tiles read and written once. A kernel that
re-reads an operand moves more and shows as a lower roofline share.

The window executor's readers (``metrics/matmul_window_roofline.py``,
``metrics/mfu.*.py``) and the GEMM-chain model (``models/gemm_chain.py``)
count with these.
"""
from __future__ import annotations

F32 = 4


def tile_grid(M: int, K: int, N: int, block) -> tuple[int, int, int]:
    """``(tile rows, tile columns, tiles)``; dims must be block multiples."""
    bm, bk, bn = block
    if M % bm or K % bk or N % bn:
        raise ValueError(f"({M},{K},{N}) is not a multiple of block {block}")
    return M // bm, N // bn, (M // bm) * (N // bn)


def window_flops(K: int, tiles: int, block) -> int:
    bm, _, bn = block
    return 2 * bm * bn * K * tiles


def window_bytes(M: int, K: int, N: int, block, start: int, tiles: int) -> int:
    bm, _, bn = block
    _, n_n, _ = tile_grid(M, K, N, block)
    idx = range(start, start + tiles)
    rows = len({t // n_n for t in idx})
    cols = len({t % n_n for t in idx})
    return F32 * (rows * bm * K + cols * K * bn + 2 * tiles * bm * bn)
