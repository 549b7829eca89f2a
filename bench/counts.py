"""Operations and bytes of the window executor's work, from shapes.

A layer is an ``(M, K) @ (K, N)`` GEMM cut into ``(bm, bn)`` output
tiles; each tile takes the whole K reduction. A window is ``w``
consecutive tiles of the row-major tile grid (``w`` the largest divisor
of the tile count not above the server's ``window_tiles``), so a
layer's windows start at multiples of ``w``.

Bytes are what the algorithm has to move for one window, in fp32: the
A rows of the tile rows it touches, the B columns of the tile columns
it touches, and its C tiles read and written once. A kernel that
re-reads an operand moves more and shows as a lower roofline share.
"""
from __future__ import annotations

F32 = 4


def tile_grid(M: int, K: int, N: int, block) -> tuple[int, int, int]:
    """``(tile rows, tile columns, tiles)``; dims must be block multiples."""
    bm, bk, bn = block
    if M % bm or K % bk or N % bn:
        raise ValueError(f"({M},{K},{N}) is not a multiple of block {block}")
    return M // bm, N // bn, (M // bm) * (N // bn)


def window_size(total_tiles: int, window_tiles: int) -> int:
    """Largest divisor of ``total_tiles`` not above ``window_tiles``."""
    w = max(1, min(window_tiles, total_tiles))
    while total_tiles % w:
        w -= 1
    return w


def layer_flops(M: int, K: int, N: int) -> int:
    return 2 * M * K * N


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


def windows_between(shapes, block, window_tiles, a, b):
    """Windows a job ran going from progress ``a`` to progress ``b``.

    ``shapes`` are the job's layers as ``(M, K, N)``; a progress is
    ``(layer, next_tile)`` with ``layer == len(shapes)`` once done.
    Yields ``(M, K, N, start, tiles)`` per window.
    """
    (la, ta), (lb, tb) = a, b
    for layer in range(la, min(lb, len(shapes) - 1) + 1):
        M, K, N = shapes[layer]
        total = tile_grid(M, K, N, block)[2]
        w = window_size(total, window_tiles)
        lo = ta if layer == la else 0
        hi = tb if layer == lb else total
        for start in range(lo, hi, w):
            yield M, K, N, start, min(w, total - start)


def tiles_done(shapes, block, progress) -> int:
    """Output tiles a job has completed at ``progress``."""
    layer, nxt = progress
    done = sum(tile_grid(*shapes[j], block)[2] for j in range(layer))
    return done + (nxt if layer < len(shapes) else 0)


def flops_done(shapes, block, progress) -> int:
    """FLOPs of the tiles a job has completed at ``progress``."""
    layer, nxt = progress
    bm, _, bn = block
    done = sum(layer_flops(*shapes[j]) for j in range(min(layer, len(shapes))))
    if layer < len(shapes):
        done += 2 * bm * bn * shapes[layer][1] * nxt
    return done
