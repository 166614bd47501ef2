"""A window store as ONE kernel: each grid streamed from HBM once.

`lazy._full_shape_plan` finds the stores (`b[1:-1, 1:-1] = f(a[...], ...)`)
whose value is built from shifted windows of arrays of the target's shape,
element-wise operators and scalars. As a select over the full shape XLA runs
such a store in one fusion, but it streams a source once per shifted window
and reads the target for the select: seven grid-sized streams for a 5-point
stencil where two are needed. Here the store is one `pallas_call` over row
blocks of the full width:

- every source is streamed once, a block of `block_rows` rows at a time, with
  the 8 rows above and below it where a read shifts that way (two more
  operands of the same array, their index maps clamped at the edges);
- a block is copied once, VMEM to VMEM, beside its halo rows and between two
  margins of 128 lanes, so that every shifted read of every 8-row strip is
  the same aligned load, a roll and a select, whatever the strip;
- the expression is evaluated strip by strip, 8 rows by at most 2048 lanes,
  in vector registers, by the function the caller hands in (the same
  operators on the same operands in the same order as outside a kernel);
- the target is written in place (`input_output_aliases`). It is streamed in
  only where the expression reads it (`a[1:, :] -= ...`) or the window leaves
  a wide border; otherwise what the store keeps of it lies in its first and
  last strip and in the first and last register of its rows, which are
  sliced off before the kernel runs, and the target itself stays in HBM;
- a strip that lies inside the window whole, as nearly all do, is stored as it
  is; the others take the target's values outside the window's mask.

What lies outside an array (above the first block, beside the first lane) is
whatever the clamped halo or the margin holds: a read can reach it only where
the window's mask drops the result, since a window read never leaves its
array. Nothing here asks where it runs: `interpret` is the caller's to pass.

On a v5e (my chip runs, PR 34): a jacobi half-step over 24,576 x 24,576
float32 7.9 ms (the select 23.9; the body alone 6.2, so HBM sets the pace:
2.1 grids at 650 GB/s), 11.1 ms with the target streamed; an fdtd step of
three stores 24.7 ms (30.9).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One 32-bit vector register: the strip the body works on, and the halo a
# read may reach into (8 rows up or down, 128 lanes left or right).
SUBLANES, LANES = 8, 128
# Stated, not left to the compiler's 16 MiB: a block of 128 rows of a
# 24,576-column grid is 12 MiB, and the pipeline holds two of each operand.
VMEM_LIMIT_BYTES = 100 * 2**20
_BLOCKS_BYTES = 88 * 2**20  # what a call's blocks may take of it
_MAX_BLOCK_ROWS = 128  # 16 halo rows beside 128: an eighth more of a source
_MIN_ROW_BLOCKS = 4  # fewer, and there is no stream to speak of
_MAX_CHUNK_LANES = 2048  # 16 registers a value: an expression stays in the file


def _reach(shifts) -> tuple[bool, bool, bool]:
    """(rows above, rows below, lanes beside) that reads at `shifts` need."""
    return (any(dr < 0 for dr, _ in shifts), any(dr > 0 for dr, _ in shifts),
            any(dc for _, dc in shifts))


def block_rows(shape, reads) -> int | None:
    """Rows of a block for a store into a 32-bit array of `shape` whose sources are
    read at `reads` (one collection of (row, lane) shifts for each source that
    is not the target), or None where the kernel does not apply: the shape is
    not whole registers, a shift leaves the halo, or no divisor of the rows
    leaves a few blocks that fit VMEM double-buffered."""
    rows, cols = shape
    if rows % SUBLANES or cols % LANES:
        return None
    if any(abs(dr) > SUBLANES or abs(dc) > LANES for shifts in reads for dr, dc in shifts):
        return None
    blocks = 4  # the target, in and out, each twice
    for shifts in reads:
        blocks += 2 + any(_reach(shifts))  # the source twice, and its copy beside the halo
    limit = min(_MAX_BLOCK_ROWS, rows // _MIN_ROW_BLOCKS,
                _BLOCKS_BYTES // (blocks * (cols + 2 * LANES) * 4))
    for candidate in range(limit - limit % SUBLANES, 0, -SUBLANES):
        if rows % candidate == 0:
            return candidate
    return None


def _chunk_lanes(cols: int) -> int:
    return max(n for n in range(LANES, min(cols, _MAX_CHUNK_LANES) + 1, LANES) if cols % n == 0)


@functools.partial(jax.jit, static_argnames=(
    "reads", "evaluate", "starts", "sizes", "rows_a_block", "target_read", "interpret"))
def window_store(target, sources, reads, scalars, evaluate, starts, sizes, rows_a_block, *,
                 target_read=True, interpret=False):
    """`target` with `evaluate(read, scalars)` stored inside the window
    (`starts`, `sizes`), everything else kept, in place.

    `sources[k]` is an array of the target's shape that the expression reads
    at the shifts `reads[k]`; `scalars` are 0-d arrays. `evaluate` is called
    once, for a strip: `read(k, shift)` gives source k's strip shifted,
    `out[i, j] = source[i + shift[0], j + shift[1]]`, `read(None, (0, 0))` the
    target's own (`target_read` says whether it asks), and the second argument
    the scalars' values. `rows_a_block` is `block_rows`' answer for this store.

    Jitted on its own, everything but the arrays static: a program that makes
    the same store twenty times (a time loop) traces and lowers ONE kernel and
    calls it twenty times, where `evaluate` is hashable by what it computes
    (`lazy._Expression`); a closure is traced anew for each call."""
    rows, cols = target.shape
    chunk = _chunk_lanes(cols)
    per_block = rows_a_block // SUBLANES
    reach = [_reach(shifts) for shifts in reads]
    # What the window leaves of the target: rows above and below, lanes left and right.
    border = (starts[0], rows - starts[0] - sizes[0], starts[1], cols - starts[1] - sizes[1])
    # Where the expression does not read the target and the border is inside
    # one strip and one register of the edges, the target is not streamed:
    # only those strips and registers of it are fetched (under a stream's
    # worth where a row is more than the two registers).
    streamed = (target_read or max(border[:2]) > SUBLANES or max(border[2:]) > LANES
                or cols <= 2 * LANES)

    block = pl.BlockSpec((rows_a_block, cols), lambda i: (i, 0))
    above = pl.BlockSpec((SUBLANES, cols), lambda i: (jnp.maximum(i * per_block - 1, 0), 0))
    below = pl.BlockSpec(
        (SUBLANES, cols), lambda i: (jnp.minimum((i + 1) * per_block, rows // SUBLANES - 1), 0))
    operands = [jnp.reshape(s, (1, 1)) for s in scalars] + [target]
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM) for _ in scalars]
    in_specs.append(block if streamed else pl.BlockSpec(memory_space=pl.ANY))
    if not streamed:
        # What is kept of a target that is not streamed lies in its first and
        # last strip and in the first and last register of every row: four
        # small arrays, sliced off before the kernel runs (a second operand of
        # the target itself would cost a copy of it, and a fetch of the kernel's
        # own would queue behind the pipeline's and hold the body up).
        strip_spec = pl.BlockSpec((SUBLANES, cols), lambda i: (0, 0))
        register_spec = pl.BlockSpec((rows_a_block, LANES), lambda i: (i, 0))
        edges = (target[:SUBLANES], target[rows - SUBLANES:], target[:, :LANES], target[:, cols - LANES:])
        for width, edge, spec in zip(border, edges, (strip_spec, strip_spec, register_spec, register_spec)):
            if width:
                operands.append(edge)
                in_specs.append(spec)
    copies = []  # a source's block beside its halo: (rows of halo, lanes of margin) or None
    scratch = []
    for source, (up, down, beside) in zip(sources, reach):
        operands.append(source)
        in_specs.append(block)
        if up:
            operands.append(source)
            in_specs.append(above)
        if down:
            operands.append(source)
            in_specs.append(below)
        halo, margin = SUBLANES * (up or down), LANES * beside
        copies.append((halo, margin) if halo or margin else None)
        if halo or margin:
            scratch.append(pltpu.VMEM((rows_a_block + 2 * halo, cols + 2 * margin), source.dtype))

    def body(*refs):
        refs = list(refs)
        scalar_values = [refs.pop(0)[0, 0] for _ in scalars]
        target_ref = refs.pop(0)
        top, bottom, left, right = (
            refs.pop(0) if width and not streamed else None for width in border)
        mains = []
        for (up, down, _), copy in zip(reach, copies):
            main = refs.pop(0)
            up_ref = refs.pop(0) if up else None
            down_ref = refs.pop(0) if down else None
            mains.append((main, up_ref, down_ref, copy))
        out_ref = refs.pop(0)
        held = []  # where the strips of each source are loaded from
        for main, up_ref, down_ref, copy in mains:
            if copy is None:
                held.append(main)
                continue
            ext, (halo, margin) = refs.pop(0), copy
            if up_ref is not None:
                ext[0:SUBLANES, margin:margin + cols] = up_ref[...]
            if down_ref is not None:
                ext[halo + rows_a_block:, margin:margin + cols] = down_ref[...]

            def copy_strip(k, carry, main=main, ext=ext, halo=halo, margin=margin):
                r0 = pl.multiple_of(k * SUBLANES, SUBLANES)
                ext[pl.ds(r0 + halo, SUBLANES), margin:margin + cols] = main[pl.ds(r0, SUBLANES), :]
                return carry

            jax.lax.fori_loop(0, per_block, copy_strip, 0)
            held.append(ext)

        first_row = pl.program_id(0) * rows_a_block

        def chunk_of_strips(q, carry):
            c0 = pl.multiple_of(q * chunk, LANES)

            def strip(k, carry):
                r0 = pl.multiple_of(k * SUBLANES, SUBLANES)
                here = (pl.ds(r0, SUBLANES), pl.ds(c0, chunk))
                sublane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, chunk), 0)
                parts = {}

                def part(k_source, strip_shift, dc):
                    """The strip `strip_shift` strips below this one (-1, 0, 1),
                    `dc` lanes to the right."""
                    key = (k_source, strip_shift, dc)
                    if key not in parts:
                        ref, copy = held[k_source], copies[k_source]
                        halo, margin = copy or (0, 0)
                        wide = ref[pl.ds(r0 + halo + SUBLANES * strip_shift, SUBLANES),
                                   pl.ds(c0, chunk + 2 * margin)]
                        if dc:
                            wide = pltpu.roll(wide, (-dc) % wide.shape[1], 1)
                        parts[key] = wide[:, margin:margin + chunk]
                    return parts[key]

                def read(k_source, shift):
                    if k_source is None:
                        return target_ref[here]
                    dr, dc = shift
                    if dr % SUBLANES == 0:
                        return part(k_source, dr // SUBLANES, dc)
                    near, far = part(k_source, 0, dc), part(k_source, 1 if dr > 0 else -1, dc)
                    by = -dr % SUBLANES  # roll(x, by)[i] = x[i + dr], rows taken round the strip
                    from_near = sublane < SUBLANES - dr if dr > 0 else sublane >= -dr
                    return jnp.where(from_near, pltpu.roll(near, by, 0), pltpu.roll(far, by, 0))

                value = evaluate(read, scalar_values)
                row0 = first_row + r0

                def with_the_border(value=value):
                    """The strip with the target's own values outside the window."""
                    row = row0 + sublane
                    lane = c0 + jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, chunk), 1)
                    if streamed:
                        inside = (row >= starts[0]) & (row < starts[0] + sizes[0])
                        inside &= (lane >= starts[1]) & (lane < starts[1] + sizes[1])
                        return jnp.where(inside, value, target_ref[here])
                    if left is not None:  # (seen only by the first chunk, as `right` by the last)
                        edge = left[here[0], :]
                        if chunk > LANES:
                            edge = jnp.concatenate([edge, value[:, LANES:]], axis=1)
                        value = jnp.where(lane < starts[1], edge, value)
                    if right is not None:
                        edge = right[here[0], :]
                        if chunk > LANES:
                            edge = jnp.concatenate([value[:, :chunk - LANES], edge], axis=1)
                        value = jnp.where(lane >= starts[1] + sizes[1], edge, value)
                    if top is not None:  # (seen only by the first strip, as `bottom` by the last)
                        value = jnp.where(row < starts[0], top[:, here[1]], value)
                    if bottom is not None:
                        value = jnp.where(row >= starts[0] + sizes[0], bottom[:, here[1]], value)
                    return value

                # Most strips lie inside the window whole: no mask, no read of the target.
                whole = []
                if border[0] or border[1]:
                    whole += [row0 >= starts[0], row0 + SUBLANES <= starts[0] + sizes[0]]
                if border[2] or border[3]:
                    whole += [c0 >= starts[1], c0 + chunk <= starts[1] + sizes[1]]
                if whole:
                    inside_whole = whole[0]
                    for condition in whole[1:]:
                        inside_whole &= condition
                    value = jax.lax.cond(inside_whole, lambda: value, with_the_border)
                out_ref[here] = value
                return carry

            return jax.lax.fori_loop(0, per_block, strip, carry)

        jax.lax.fori_loop(0, cols // chunk, chunk_of_strips, 0)

    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct(target.shape, target.dtype),
        grid=(rows // rows_a_block,),
        in_specs=in_specs,
        out_specs=block,
        scratch_shapes=scratch,
        input_output_aliases={len(scalars): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="shim_window_store",
    )(*operands)
