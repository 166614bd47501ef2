"""Lazy fusion engine for the numpy dispatch shim.

Eager op-at-a-time dispatch is the wrong shape for XLA: every op pays a
dispatch/round-trip cost and materializes its output in HBM. This module makes
TpuArray operations build an expression DAG instead; when a value is actually
needed (float(), print, np.asarray, control flow), the whole graph is compiled
ONCE by jax.jit into a single fused XLA computation and executed. Graphs with
identical structure (same ops, statics, leaf shapes/dtypes, outputs and
donated leaves) share one compiled executable via a structure-keyed cache, and
jit executables persist across sandbox processes through the JAX compilation
cache.

Effect: `a = np.random.rand(N); s = (a*a).sum(); float(s)` is one XLA
execution instead of three, and re-running the same program shape skips
tracing entirely: the compiled runner by the graph's structure, and each
node's shape and dtype by its op, its operands' shapes and its statics
(`_aval_memo`), so that building the graph asks `jax.eval_shape` nothing.

A time loop (`for t in range(T): a[1:-1] = f(b); b[1:-1] = f(a)`) grows one
graph until it holds MAX_GRAPH_NODES nodes; the op that crosses the cap
flushes: its lazy operands are computed by ONE program (counted in
`counters.flushes`), every array the user still holds is written back, and the
loop goes on from concrete leaves. A node that a program computed keeps its
value and lets go of its operands, so nothing is computed twice and the
history below it is freed at once. A concrete leaf whose only holder is the
graph that overwrites it (`a[idx] = v`, `a += v`, `a = a + 1`) is DONATED to
the program that consumes it: the output takes its buffer, and a loop over
arrays that fill a quarter of the chip holds them once, not twice. What may be
donated is read from what can be observed, reference counts and owners; an
array the user holds under another name or through a lazy view is never
donated, so numpy's call-time value semantics hold.

A WINDOW store in such a loop (`b[1:-1, 1:-1] = 0.2 * (a[1:-1, 1:-1] + a[1:-1,
:-2] + ...)`: a full-rank tuple of step-1 slices on both sides) is traced as
ONE pass over the array's full, aligned shape: each window read is its array
shifted by the read's origin less the store's (one `lax.pad` with negative
edges, which XLA keeps inside the fusion that reads it), the operators run at
the full shape on the same operands in the same order, and the store is a
select against the window's mask, so the program's output aliases the donated
target. XLA's own program for the slices and the scatter is two passes that
the tiling does not align: the stencil into a window-shaped temporary, then a
`dynamic-update-slice` of it one element in. `_full_shape_plan` decides, once
per runner and from the index and the shapes alone; a store whose value is a
scalar or broadcasts, whose index is strided, fancy or of reduced rank, whose
window is under half the array, whose expression holds anything but windows of
arrays of the target's shape, element-wise operators, scalars and 0-d arrays,
or any node of which something else reads (a view the user holds) keeps
`setitem_op` / `getitem_op` as they are. `counters.aligned_stores` counts the
stores that ran in the full-shape form.

That one fusion still streams the read array from HBM once per shifted window
and reads the target for the select: seven array-sized streams for a 5-point
stencil. Where the program runs on a TPU (`_platform`: the device its leaves
live on) and the store is one the kernel takes (`_kernel_plan`: two
dimensions of whole registers, 32 bits an element throughout, every shift
within 8 rows and 128 lanes, the target read at shift zero if at all, rows
enough for four blocks), the whole store is ONE Pallas kernel instead
(`stencil.window_store`): every source streamed once in row blocks with its
halo rows in VMEM, the expression evaluated strip by strip by the SAME
functions in the SAME order (`_Expression`), the target written in place and
read only where the expression reads it or the window's border is wide. One
plan, two lowerings, chosen by what can be observed; no option. A kernel the
chip's compiler refuses never fails a turn: `_run` builds the program again
with every store as a select. `counters.kernel_stores` counts the stores that
ran as a kernel. So that an in-place kernel finds its target's buffer, a
program returns its outputs in the order in which jax pairs them with the
donated leaves they were stored into (`_paired`).

`counters` counts what the shim did since it was last taken (the warm runner
takes it at the end of each turn and stamps it into the reply), and times the
stages of a turn's user code: numpy's reads of files (`read`), the copies to
the device (`ship`), the shim's own host work, the calls that hand the device
a program (`dispatched`), the waits for a value (`wait`) and the copies back
(`fetch`).
"""

from __future__ import annotations

import logging
import math
import sys
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as real_np

from . import stencil

logger = logging.getLogger(__name__)

# Cap on nodes in a single graph. The op that would cross it flushes: its lazy
# operands are computed (one program, one `flushes` count) and it is built on
# concrete leaves, so an unbounded program loop runs as a chain of fused
# programs of at most this many nodes, never as one graph that grows until
# tracing it is the bottleneck. A loop's flush falls at the same op in every
# turn that runs the same source, so its programs are the same from turn to
# turn and hit the runner cache.
MAX_GRAPH_NODES = 200

_REF_NODE = 0
_REF_LEAF = 1
_REF_STATIC = 2


class Counters:
    """What the shim did since it was last taken. The warm runner takes it at
    the end of a turn's user code and zeroes it on /reset; the control plane
    stamps it into Result.phases as `shim_<name>`, a `_s` left off (`host_s` as
    `shim_host`): `code_executor.SHIM_PHASES`. THE list of the fields and
    what each means is this one; `FIELDS` is read from `reset`.

    The six `_s` fields are the STAGES of a turn's user code: every second
    the shim spends lies in exactly one of them (a stage that runs inside
    another's clock is left out of it, `spent`), so that with the
    remainder (the user's own interpreter, stock numpy on host arrays,
    fallbacks) they tile the runner's `user_code` stage. Each but `host_s`
    is also an annotation of a running capture (`shim.load`, `shim.h2d`,
    `shim.materialize` for the dispatch, `shim.wait`, `shim.d2h`).

    programs           executions of a compiled runner
    exec_cache_misses  runners built, that is traced (a clear-all of the runner
                       cache at its limit shows here as the misses that follow)
    nodes              nodes those programs executed
    flushes            materializations forced by MAX_GRAPH_NODES
    load_files         calls of numpy's own `fromfile`, `load` and `frombuffer`
                       under the shim (`read`): each file or buffer read,
                       whether its array was then placed on the device or
                       stayed numpy's (under the threshold, a wide integer)
    load_bytes         `nbytes` of the arrays those calls returned (an `.npz`
                       archive, whose members numpy reads later, counts 0)
    load_s             seconds inside those calls: numpy's read alone, before
                       the array is placed
    h2d_arrays         host arrays shipped to the device, each copy one: by
                       `ship`, the one place the shim makes such a copy (an
                       array placed when it is read from a file, an ndarray
                       operand of a node or of an eager call, alone or inside
                       a list or tuple). Not the `bins + 1` edges of a
                       histogram, which the shim makes itself and hands to
                       its program as an operand
    h2d_bytes          the bytes of those copies, as they lie on the device
    h2d_s              seconds inside those copies, until the runtime has taken
                       the bytes: the host's part. The copy over the link goes
                       on behind it and shows in `wait_s` of whoever asks
                       for a value that needs it
    donated_bytes      leaves donated to the program that consumed them
    aligned_stores     window stores (`a[1:-1, 1:-1] = f(b[...])`) that a
                       program executed over the array's full shape
                       (`_full_shape_plan`), as a select or as a kernel,
                       counted per execution
    kernel_stores      those of them that ran as one Pallas kernel, each
                       grid streamed once (`stencil.window_store`); the rest
                       ran as one select fusion
    histograms         calls of `np.histogram` that ran as the shim's one program
                       (`shim._histogram_program`: every element compared
                       against numpy's own edges, the bins summed in the same
                       program); a call that kept `jnp.histogram` or numpy is
                       not counted
    dots               dot-like nodes (`dot`, `matmul`, `@`, `inner`, `tensordot`,
                       `einsum`: DOT_OPS) that a program executed, counted
                       per execution
    dot_flops          what those contractions ask of the chip: 2 x the elements
                       of each result x the product of the contracted
                       dimensions, read from the nodes' shapes (`_dot_flops`)
    ufunc_methods      calls of a numpy ufunc's method (`np.add.outer`,
                       `np.minimum.reduce`, `np.maximum.accumulate`, `np.add.at`)
                       that ran on the device as the `jnp` ufunc's own method:
                       a node of a program, counted when it is executed, or an
                       eager call. One that ran under numpy on host copies of
                       device arrays is a fallback
    fallbacks          calls the shim routed to the device that ran under
                       stock numpy after all (`np.fromfunction` of a function
                       a TpuArray cannot serve, a jnp function that refused
                       its arguments): correct, and seconds of host numpy
    host_s             seconds inside `build_node` and `materialize`, less what
                       the other stages took inside them (the compiled
                       runner's call, a leaf shipped on the way)
    dispatch_s         seconds inside the calls that hand the device a program
                       (`dispatched`): a compiled runner's (`_run`), an eager
                       `jnp` call's (`shim.eager_device`), the histogram
                       program's. The enqueue; on a miss of the runner cache
                       the trace and XLA's compile
    wait_s             seconds the host was blocked until a device value it
                       had asked for was ready: `block_until_ready` on the
                       forced array, in `fetch` (between asking for the copy
                       and taking it) and in `TpuArray.block_until_ready`
    d2h_arrays         device arrays copied to the host, each copy one: by
                       `fetch`, the one place the shim makes such a copy
                       (`__array__`, `float`, `print`, `tolist`, `tofile`, ...)
    d2h_bytes          the bytes of those copies
    d2h_s              seconds inside those copies, after the wait
    """

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self.programs = self.exec_cache_misses = self.nodes = self.flushes = 0
        self.load_files = self.load_bytes = 0
        self.load_s = 0.0
        self.h2d_arrays = self.h2d_bytes = 0
        self.h2d_s = 0.0
        self.donated_bytes = self.aligned_stores = self.kernel_stores = self.histograms = 0
        self.dots = self.dot_flops = self.ufunc_methods = self.fallbacks = 0
        self.host_s = self.dispatch_s = self.wait_s = 0.0
        self.d2h_arrays = self.d2h_bytes = 0
        self.d2h_s = 0.0
        self._depth = 0  # build_node -> flush -> materialize nest: count once
        self._entered = self._outside = 0.0

    def take(self) -> dict:
        taken = {name: round(value, 6) if name.endswith("_s") else value
                 for name, value in vars(self).items() if not name.startswith("_")}
        self.reset()
        return taken

    def spent(self, stage: str, since: float) -> None:
        """The seconds since `since` (a `perf_counter` reading) are `stage`'s;
        where they passed inside the clock of `host_s`, they are not its too."""
        seconds = time.perf_counter() - since
        setattr(self, stage, getattr(self, stage) + seconds)
        if self._depth:
            self._outside += seconds

    def __enter__(self) -> None:
        self._depth += 1
        if self._depth == 1:
            self._entered = time.perf_counter()

    def __exit__(self, *exc) -> None:
        self._depth -= 1
        if self._depth == 0:
            self.host_s += time.perf_counter() - self._entered - self._outside
            self._outside = 0.0


Counters.FIELDS = tuple(name for name in vars(Counters()) if not name.startswith("_"))
counters = Counters()


def read(np_fn, *args, **kwargs):
    """`np_fn(*args, **kwargs)`, one of numpy's own reads of a file or a
    buffer (`fromfile`, `load`, `frombuffer`), counted and timed before the
    array is placed; inside a capture, seen (`shim.load`)."""
    started = time.perf_counter()
    with jax.profiler.TraceAnnotation("shim.load"):
        loaded = np_fn(*args, **kwargs)
    counters.spent("load_s", started)
    counters.load_files += 1
    counters.load_bytes += getattr(loaded, "nbytes", 0)
    return loaded


def ship(host, dtype=None) -> jax.Array:
    """`host`, an ndarray, as an array on the device: the one place the shim
    copies from the host, so that every copy is counted, timed and, inside a
    capture, seen (`shim.h2d`, a no-op outside one). Raises what `jnp.asarray`
    raises of a dtype the device has none for."""
    started = time.perf_counter()
    with jax.profiler.TraceAnnotation("shim.h2d"):
        shipped = jnp.asarray(host, dtype=dtype)
    counters.spent("h2d_s", started)
    counters.h2d_arrays += 1
    counters.h2d_bytes += shipped.nbytes
    return shipped


def dispatched(fn, *args, **kwargs):
    """`fn(*args, **kwargs)`, a call that hands the device a program: its
    seconds are `dispatch_s` (the enqueue; a first call's trace and compile)."""
    started = time.perf_counter()
    try:
        return fn(*args, **kwargs)
    finally:
        counters.spent("dispatch_s", started)


def wait(arr: jax.Array) -> jax.Array:
    """`arr` once the device has made it: the host blocked meanwhile, counted
    in `wait_s` and, inside a capture, seen (`shim.wait`)."""
    started = time.perf_counter()
    with jax.profiler.TraceAnnotation("shim.wait"):
        arr.block_until_ready()
    counters.spent("wait_s", started)
    return arr


def fetch(arr: jax.Array) -> real_np.ndarray:
    """`arr`, an array on the device, as an ndarray on the host: the one
    place the shim copies to the host, beside `ship` for the way there, so
    that every copy is counted, timed and, inside a capture, seen (`shim.d2h`).
    The wait for the value comes first and is a stage of its own (`wait`):
    only call this where the value was about to be copied out anyway. The
    copy is ASKED for before the wait, as `np.asarray` alone would ask: the
    runtime starts it when the value is made, with no trip through the host
    in between, and `d2h_s` is what of it is left after the wait."""
    arr.copy_to_host_async()
    wait(arr)
    started = time.perf_counter()
    with jax.profiler.TraceAnnotation("shim.d2h"):
        host = real_np.asarray(arr)
    counters.spent("d2h_s", started)
    counters.d2h_arrays += 1
    counters.d2h_bytes += host.nbytes
    return host


def fetch_scalar(arr: jax.Array, convert):
    """`convert(arr)` for `float`, `int`, `bool` and `complex`, through
    `fetch`. What jax would refuse before it copied anything (an array that
    is no scalar) it refuses here: its own error."""
    if arr.ndim and not (convert is bool and arr.size == 1):
        return convert(arr)
    return convert(fetch(arr))


class Node:
    """One operation in the lazy DAG."""

    __slots__ = ("op_name", "fn", "arg_refs", "kwargs", "aval", "n_nodes",
                 "owners", "value")

    def __init__(self, op_name, fn, arg_refs, kwargs, aval, n_nodes):
        self.op_name = op_name
        self.fn = fn
        # arg_refs: list of (kind, value) — kind NODE -> Node, LEAF -> jax/np
        # array, STATIC -> hashable python value
        self.arg_refs = arg_refs
        self.kwargs = kwargs  # static-only
        self.aval = aval  # jax.ShapeDtypeStruct
        self.n_nodes = n_nodes
        # weakrefs to TpuArrays currently backed by this node; when a graph
        # containing this node materializes, their values are written back so
        # user-held arrays become concrete instead of being recomputed by the
        # next expression that uses them.
        self.owners: list = []
        # What a program computed for this node, once one has: the node is a
        # leaf from then on and holds no operand (`arg_refs` is emptied), so a
        # lazy graph that still points at it neither runs its history again
        # nor keeps that history's buffers alive.
        self.value = None

    def live_owners(self):
        return [o for ref in self.owners if (o := ref()) is not None
                and o._node is self]


_MAX_STATIC_CONTAINER = 64


def _static_ok(value) -> bool:
    if isinstance(value, (int, float, bool, complex, str, bytes, type(None))):
        return True
    if isinstance(value, (tuple, list)):
        # Big literal containers must become device leaves, not baked
        # constants with megabyte repr() cache keys.
        return len(value) <= _MAX_STATIC_CONTAINER and all(
            _static_ok(v) for v in value
        )
    if isinstance(value, slice):
        return _static_ok((value.start, value.stop, value.step))
    if isinstance(value, (type, real_np.dtype)) or value is Ellipsis:
        return True
    if isinstance(value, real_np.generic):
        return True
    return False


def _static_key(value) -> str:
    # Type-qualified: python 2.0 and np.float64(2.0) repr identically but
    # trace to different dtypes, so they must not share a cached runner.
    if isinstance(value, (tuple, list)):
        inner = ",".join(_static_key(v) for v in value)
        return f"{type(value).__name__}({inner})"
    return f"{type(value).__name__}:{value!r}"


def _below(nodes, skip=()):
    """Each node under `nodes` once, `skip` (by id) left out: shared
    subexpressions (diamonds, x+x chains) count once, matching what actually
    gets compiled. A node that holds its value has no operands left: the walk
    ends at it. (A generator of its own, so that no local of `_build_node`
    still points at a node when a flush asks who does.)"""
    seen = set(skip)
    stack = list(nodes)
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        yield node
        stack.extend(v for kind, v in node.arg_refs if kind == _REF_NODE)


def _operands(arg_refs) -> list:
    return [v for kind, v in arg_refs if kind == _REF_NODE]


# What `jax.eval_shape` answered for an op, by the op, its operands' shapes and
# dtypes and its statics: a turn that runs the same source builds the same few
# hundred nodes as the turn before, and an abstract evaluation is most of the
# host time of building one (0.7 ms; ten times that under the profiler's python
# tracer). Only answers are kept, never a failure. Cleared whole at its limit,
# like the runner cache.
_aval_memo: dict[tuple, Any] = {}
_AVAL_MEMO_LIMIT = 4096


def _aval_key(fn, abstract_args, kwargs) -> tuple:
    parts = tuple(
        (tuple(a.shape), str(a.dtype), bool(getattr(a, "weak_type", False)))
        if isinstance(a, jax.ShapeDtypeStruct) else _static_key(a)
        for a in abstract_args
    )
    return (fn, parts, _static_key(sorted(kwargs.items())), bool(jax.config.jax_enable_x64))


def build_node(op_name: str, fn: Callable, args, kwargs) -> Node | None:
    """Try to create a lazy node; None means 'do it eagerly instead'.

    `args` may contain TpuArray (lazy or concrete), jax/np arrays, and
    statics. kwargs must be static.
    """
    with counters:
        return _build_node(op_name, fn, args, kwargs)


def _build_node(op_name: str, fn: Callable, args, kwargs) -> Node | None:
    from .shim import TpuArray

    for v in kwargs.values():
        if not _static_ok(v):
            return None

    arg_refs: list[tuple[int, Any]] = []
    abstract_args = []
    for a in args:
        if isinstance(a, TpuArray):
            node = a._node
            if node is not None:
                arg_refs.append((_REF_NODE, node))
                abstract_args.append(node.aval)
            else:
                arr = a._concrete
                arg_refs.append((_REF_LEAF, arr))
                abstract_args.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))
        elif isinstance(a, (jax.Array, real_np.ndarray)):
            arg_refs.append((_REF_LEAF, a))
            abstract_args.append(jax.ShapeDtypeStruct(a.shape, a.dtype))
        elif _static_ok(a):
            arg_refs.append((_REF_STATIC, a))
            abstract_args.append(a)
        else:
            return None

    # Unique-node count: per-reference summing would inflate exponentially
    # and force early materializations.
    n_nodes = 1 + sum(1 for _ in _below(_operands(arg_refs)))

    if n_nodes > MAX_GRAPH_NODES:
        # Flush, in ONE program (an operand forced alone would leave the
        # others pointing at a history to run again): the arrays the user
        # holds below the operands, so that an expression cut in two by the
        # cap writes out no temporary; or, where that is not what made the
        # graph big, the operands themselves. Every owner is written back,
        # then the op is built again on what is concrete now.
        counters.flushes += 1
        operands = [
            a._node for a in args
            if isinstance(a, TpuArray) and a._node is not None
        ]
        materialize_all(_held_below(operands) or operands)
        return _build_node(op_name, fn, args, kwargs)

    memo_key = _aval_key(fn, abstract_args, kwargs)
    try:
        aval = _aval_memo.get(memo_key)
    except TypeError:  # a callable object that does not hash: asked every time
        memo_key = aval = None
    if aval is None:
        def abstract_call(*arrays):
            it = iter(arrays)
            call_args = [
                next(it) if kind != _REF_STATIC else value
                for kind, value in arg_refs
            ]
            return fn(*call_args, **kwargs)

        arrays_only = [a for a in abstract_args if isinstance(a, jax.ShapeDtypeStruct)]
        try:
            aval = jax.eval_shape(abstract_call, *arrays_only)
        except Exception:  # noqa: BLE001 — anything weird: run it eagerly
            return None
        if memo_key is not None:
            if len(_aval_memo) >= _AVAL_MEMO_LIMIT:
                _aval_memo.clear()
            _aval_memo[memo_key] = aval
    if not isinstance(aval, jax.ShapeDtypeStruct):
        return None  # multi-output ops stay eager

    # Snapshot host ndarray leaves LAST, once the node is certain to be built
    # (cap-retry and eval_shape bail-outs above must not waste transfers):
    # numpy semantics read operand values at CALL time, so in-place mutation
    # of the caller's array between build and forcing must not leak in.
    # Transferring to device is the same move materialize() would do anyway;
    # the memo keeps np.op(h, h) deduped to one leaf/transfer.
    host_memo: dict[int, Any] = {}
    for i, (kind, value) in enumerate(arg_refs):
        if kind == _REF_LEAF and isinstance(value, real_np.ndarray):
            snapshot = host_memo.get(id(value))
            if snapshot is None:
                try:
                    snapshot = host_memo[id(value)] = ship(value)
                except (TypeError, ValueError):
                    return None  # e.g. object dtype: run eagerly instead
            arg_refs[i] = (_REF_LEAF, snapshot)
    return Node(op_name, fn, arg_refs, kwargs, aval, n_nodes)


# --------------------------------------------------------------------------
# Matmul precision for SHIM-DISPATCHED computations only (set by
# npdispatch.install from APP_NUMPY_DISPATCH_MATMUL_PRECISION). numpy users
# expect float32 matmuls to be float32 — the MXU would otherwise run bf16
# passes and round (257.0 -> 256.0) — but this must NOT be a global
# jax_default_matmul_precision: user jax code sharing the process would
# silently change numerics/speed, and Pallas kernels break outright (bf16
# dots lower with an fp32 contract precision Mosaic rejects). Every shim
# execution path enters this scope instead.
MATMUL_PRECISION = "highest"


def precision_scope():
    return jax.default_matmul_precision(MATMUL_PRECISION)


# Materialization: linearize DAG -> structure key -> cached jitted runner.

class _Program(NamedTuple):
    """A compiled runner and what one execution of it counts."""

    runner: Callable
    returned: list  # the nodes whose values it returns, in its order
    aligned_stores: int  # the window stores it runs at full shape
    kernel_stores: int  # those of them as a kernel
    dots: int
    dot_flops: int
    ufunc_methods: int


# structure key -> its program
_exec_cache: dict[tuple, _Program] = {}
_CACHE_LIMIT = 512


def _held_below(operands: list[Node]) -> list[Node]:
    """The nodes under `operands`, themselves left out, that a live TpuArray
    points at: what the user holds of an expression's history."""
    below = [v for node in operands for v in _operands(node.arg_refs)]
    return [
        node for node in _below(below, skip={id(node) for node in operands})
        if node.value is None and node.live_owners()
    ]


class _Linear:
    """A DAG in topological order (operands before the node that reads them).

    spec: per node, (fn, [(kind, index_or_static)], kwargs)
    leaves: deduped concrete arrays in first-seen order; a node below the
      roots that holds its value is the leaf it holds
    nodes: the Node object at each spec index
    key: structural tuple — equal keys guarantee the same spec shape
    and, for `_outputs` and `_donatable`, who points at what INSIDE this
    graph: references to each node from the nodes above it, references to
    each leaf from `arg_refs`, and the value-holding nodes with the
    references to them.
    """

    __slots__ = ("spec", "leaves", "nodes", "key", "node_refs", "leaf_refs",
                 "holders", "holder_leaf", "holder_refs")

    def __init__(self, roots: list[Node]) -> None:
        self.spec: list[tuple] = []
        self.leaves: list[Any] = []
        self.nodes: list[Node] = []
        self.node_refs: list[int] = []
        self.leaf_refs: list[int] = []
        self.holders: dict[int, Node] = {}  # by id: the value-holding nodes,
        self.holder_leaf: dict[int, int] = {}  # the leaf each holds,
        self.holder_refs: dict[int, int] = {}  # and the references to it
        node_index: dict[int, int] = {}
        leaf_index: dict[int, int] = {}
        key_parts: list[tuple] = []

        def leaf_of(value) -> int:
            li = leaf_index.get(id(value))
            if li is None:
                li = leaf_index[id(value)] = len(self.leaves)
                self.leaves.append(value)
                self.leaf_refs.append(0)
            return li

        # Post-order without recursion: a loop's graph is a chain as deep as
        # the cap, and a closure that calls itself would be a reference cycle
        # that keeps `leaves` alive until the cyclic collector runs.
        stack = [(root, False) for root in reversed(roots)]
        while stack:
            node, expanded = stack.pop()
            if id(node) in node_index:
                continue
            if not expanded:
                stack.append((node, True))
                stack.extend(
                    (v, False) for kind, v in reversed(node.arg_refs)
                    if kind == _REF_NODE and v.value is None and id(v) not in node_index
                )
                continue
            idx = len(self.spec)
            refs, ref_keys = [], []
            for kind, value in node.arg_refs:
                if kind == _REF_NODE and value.value is None:
                    child = node_index[id(value)]
                    self.node_refs[child] += 1
                    refs.append((_REF_NODE, child))
                    ref_keys.append(("n", child))
                elif kind == _REF_STATIC:
                    refs.append((_REF_STATIC, value))
                    ref_keys.append(("s", _static_key(value)))
                else:
                    if kind == _REF_NODE:  # it holds its value: a leaf
                        holder, value = value, value.value
                        li = leaf_of(value)
                        self.holders[id(holder)] = holder
                        self.holder_leaf[id(holder)] = li
                        self.holder_refs[id(holder)] = self.holder_refs.get(id(holder), 0) + 1
                    else:
                        li = leaf_of(value)
                        self.leaf_refs[li] += 1
                    refs.append((_REF_LEAF, li))
                    ref_keys.append(("l", li, tuple(value.shape), str(value.dtype)))
            node_index[id(node)] = idx
            self.nodes.append(node)
            self.node_refs.append(0)
            self.spec.append((node.fn, refs, node.kwargs))
            key_parts.append(
                (node.op_name, tuple(ref_keys), _static_key(sorted(node.kwargs.items())))
            )
        self.key = tuple(key_parts)


def _refs_beyond(container, key) -> int:
    """How many references `container[key]` has besides the container's own.
    The one place that reads sys.getrefcount: the object is never bound to a
    name here, so neither a caller's locals nor a tracer's frame can add to
    the count, and what the call itself adds is measured at import, below,
    not counted by hand."""
    return sys.getrefcount(container[key]) - _REFS_OF_THE_CALL


_REFS_OF_THE_CALL = 0
_REFS_OF_THE_CALL = _refs_beyond([object()], 0)


def _outputs(lin: _Linear, roots: list[Node]) -> list[int]:
    """Spec indices of the nodes whose values the program returns: the roots;
    any node some live TpuArray still points at, so that its owner gets the
    computed value written back and user-held intermediates become concrete
    instead of being recomputed by the next expression that uses them; and
    any node that something outside this graph points at (`_refs_beyond`
    against the references `_Linear` counted inside it), which is a lazy
    expression still pending: it reads the value when its turn comes and
    does not run this graph's history again. Every way into the graph from
    outside then ends at a node that holds its value."""
    root_ids = {id(root) for root in roots}
    return [
        i for i in range(len(lin.nodes))
        if _refs_beyond(lin.nodes, i) > lin.node_refs[i]
        or id(lin.nodes[i]) in root_ids or lin.nodes[i].live_owners()
    ]


def _donatable(lin: _Linear, out_indices: list[int]) -> list[int]:
    """Indices of the leaves that may be donated to this graph's program:
    device arrays that nothing outside the graph can reach, each paired with
    an output of its shape and dtype (jax aliases no other, and warns).

    Decided from what can be observed. A leaf is out of reach when every
    reference to it is one this graph holds (`_refs_beyond` against the
    count `_Linear` took): a TpuArray that shares it, a lazy view of it, a
    `device_array` the user kept are each one reference more. The graph
    itself never reads it again: `_outputs` leaves no way in from outside
    but through a node that holds its value. A value-holding node below the
    roots counts as one reference to the leaf it holds, if nothing outside
    points at that node either. (One thread: a second one that holds an
    array in a frame is one reference more, and the array is not donated.)
    """
    held = [0] * len(lin.leaves)  # holders of each leaf; -1: one of them is reachable
    for key, li in lin.holder_leaf.items():
        if _refs_beyond(lin.holders, key) > lin.holder_refs[key]:
            held[li] = -1
        elif held[li] >= 0:
            held[li] += 1
    free_outputs: dict[tuple, int] = {}
    for i in out_indices:
        aval = lin.nodes[i].aval
        slot = (tuple(aval.shape), str(aval.dtype))
        free_outputs[slot] = free_outputs.get(slot, 0) + 1
    donated = []
    for li in range(len(lin.leaves)):
        if held[li] < 0 or _refs_beyond(lin.leaves, li) > lin.leaf_refs[li] + held[li]:
            continue
        leaf = lin.leaves[li]  # (bound only now: a name is a reference)
        if not isinstance(leaf, jax.Array):
            continue
        slot = (tuple(leaf.shape), str(leaf.dtype))
        if free_outputs.get(slot, 0) > 0:
            free_outputs[slot] -= 1
            donated.append(li)
    return donated


def _paired(lin: _Linear, out_indices: list[int], donated: list[int]) -> list[int]:
    """`out_indices` in the order the program returns them. jax gives the k-th
    donated argument of a shape and dtype the buffer of the k-th output of that
    shape and dtype, whatever made it; so the output that a chain of stores
    makes of a donated leaf (`a[idx] = v`, again and again) is put where it is
    paired with that leaf, and the store runs in place. Any other order is as
    correct and costs a copy of the array on the way in and one on the way
    out wherever an in-place kernel writes it."""
    def slot(aval):
        return tuple(aval.shape), str(aval.dtype)

    made_of: dict[int, int] = {}  # a donated leaf: the position of the last output stored into it
    for at, i in enumerate(out_indices):
        ref = (_REF_NODE, i)
        while ref[0] == _REF_NODE and lin.spec[ref[1]][0] is setitem_op:
            ref = lin.spec[ref[1]][1][0]
        if ref[0] == _REF_LEAF and ref[1] in donated:
            made_of[ref[1]] = at
    if not made_of:
        return out_indices
    order = list(range(len(out_indices)))
    for key in {slot(lin.leaves[li]) for li in made_of}:
        places = [at for at in order if slot(lin.nodes[out_indices[at]].aval) == key]
        others = [at for at in places if at not in made_of.values()]
        ranked = []
        for li in donated:
            if slot(lin.leaves[li]) == key and (li in made_of or others):
                ranked.append(made_of[li] if li in made_of else others.pop(0))
        for place, at in zip(places, ranked + others):
            order[place] = at
    return [out_indices[at] for at in order]


# The element-wise operator functions: what `TpuArray`'s binary and unary
# operators call. Filled by shim.py where its operator tables are defined.
ELEMENTWISE_OPS: list = []

_SCALARS = (int, float, bool, complex, real_np.generic)


def _window(idx, shape):
    """(starts, sizes) where `idx` takes a window of an array of `shape`: one
    step-1 slice for every axis, none of them empty. None for anything else."""
    if isinstance(idx, slice) and len(shape) == 1:
        idx = (idx,)
    if not isinstance(idx, tuple) or len(idx) != len(shape) or not shape:
        return None
    starts, sizes = [], []
    for part, n in zip(idx, shape):
        if not isinstance(part, slice):
            return None
        try:
            start, stop, step = part.indices(n)
        except TypeError:  # a bound that is no index
            return None
        if step != 1 or stop <= start:
            return None
        starts.append(start)
        sizes.append(stop - start)
    return tuple(starts), tuple(sizes)


class _Expression(NamedTuple):
    """A store's value as a kernel evaluates it on one strip: a step for each
    node of the expression, operands first. A read is (None, the source's
    slot or None for the target, the shift); an operator is (its function,
    its operands, its keyword arguments as sorted items), an operand one of
    ("tile", None, an earlier step), ("scalar", None, a 0-d array's slot) and
    ("static", its type, the value): 2.0 and np.float64(2.0) are equal and
    trace apart. Hashable by what it holds, so that the stores of one
    structure (a time loop's, the same every step) are traced and lowered to
    ONE kernel, called once for each."""

    steps: tuple

    @property
    def reads_target(self) -> bool:
        return any(fn is None and slot is None for fn, slot, _ in self.steps)

    def __call__(self, read, scalars):
        tiles = []
        for fn, operands, kwargs in self.steps:
            if fn is None:  # a read: the source's slot and the shift are in the operator's places
                tiles.append(read(operands, kwargs))
                continue
            tiles.append(fn(*[
                tiles[v] if kind == "tile" else scalars[v] if kind == "scalar" else v
                for kind, _, v in operands
            ], **dict(kwargs)))
        return tiles[-1]


class _Kernel(NamedTuple):
    """A window store as one `stencil.window_store`: what `_kernel_plan` read
    from the store's expression. References are `_Linear.spec`'s (kind, index)."""

    nodes: tuple  # the expression's nodes: evaluated inside the kernel and nowhere else
    target: tuple
    sources: tuple  # the distinct arrays the expression reads, the target left out
    reads: tuple  # for each of them, the shifts it is read at
    scalars: tuple  # the 0-d arrays among the operators' operands
    expression: _Expression
    block_rows: int


def _kernel_plan(lin: _Linear, s: int, tree: dict) -> _Kernel | None:
    """Store `s`, whose expression `tree` (`_full_shape_plan`) qualified for
    the full shape, as a kernel; None keeps the select. Read from the shapes,
    the dtypes and the shifts: two dimensions of whole registers and enough
    rows for a few blocks (`stencil.block_rows`); 32 bits an element in every
    array, every 0-d operand (strongly typed) and every intermediate, booleans
    allowed between; every shift inside the halo; and the target read at
    shift zero only, since the kernel writes it block by block while later
    blocks still read their halos."""
    def aval(ref):
        kind, v = ref
        return lin.nodes[v].aval if kind == _REF_NODE else lin.leaves[v]

    def word(a, or_bool=False) -> bool:
        return (a.dtype.itemsize == 4 and a.dtype.kind in "fiu") or (or_bool and a.dtype == bool)

    target = lin.spec[s][1][0]
    full = tuple(aval(target).shape)
    if len(full) != 2 or not word(aval(target)):
        return None
    nodes = sorted(tree)  # (operands before the node that reads them: `_Linear`'s order)
    sources, reads, scalars, steps = [], [], [], []
    for i in nodes:
        fn, refs, kwargs = lin.spec[i]
        if not word(lin.nodes[i].aval, or_bool=True):
            return None
        if tree[i] is not None:  # a read
            source, slot = refs[0], None
            if source == target:
                if any(tree[i]):
                    return None
            else:
                if source not in sources:
                    if not word(aval(source)):
                        return None
                    sources.append(source)
                    reads.append(set())
                slot = sources.index(source)
                reads[slot].add(tree[i])
            steps.append((None, slot, tree[i]))
            continue
        operands = []
        for ref in refs:
            if ref[0] == _REF_STATIC:
                operands.append(("static", type(ref[1]), ref[1]))
            elif ref[0] == _REF_NODE and ref[1] in tree:
                operands.append(("tile", None, nodes.index(ref[1])))
            else:
                if not word(aval(ref)) or getattr(aval(ref), "weak_type", False):
                    return None
                if ref not in scalars:
                    scalars.append(ref)
                operands.append(("scalar", None, scalars.index(ref)))
        steps.append((fn, tuple(operands), tuple(sorted(kwargs.items()))))
    rows = stencil.block_rows(full, reads)
    if rows is None:
        return None
    expression = _Expression(tuple(steps))
    try:
        hash(expression)
    except TypeError:  # a static that does not hash
        return None
    return _Kernel(tuple(nodes), target, tuple(sources), tuple(tuple(sorted(r)) for r in reads),
                   tuple(scalars), expression, rows)


def _full_shape_plan(lin: _Linear, out_indices: list[int], platform: str = ""):
    """Which window stores of `lin` are computed over their array's full,
    aligned shape, which window reads are shifted for them, and which of the
    stores run as a kernel on `platform`:
    ({index of a getitem: its origin less the store's}, {index of a setitem:
    (the window's starts, its sizes)}, {index of a setitem: its `_Kernel`}).

    XLA writes `a[1:-1, 1:-1] = f(b[...])` as a stencil into a window-shaped
    temporary and a `dynamic-update-slice` of it at an offset the tiling does
    not share: two misaligned passes. Over the full shape, with each read as
    its array shifted and the store as a select against the window's mask, it
    is ONE fusion whose output aliases the target (`_shifted`, `_select_window`).

    A store qualifies by what can be read from its index and the shapes: a
    window of at least half of its array, whose value has the target's dtype
    and is built only from equal-shaped windows of arrays of the target's full
    shape, the element-wise operators (ELEMENTWISE_OPS) over them, python
    scalars and 0-d arrays; and nothing but the store reads any node of that
    expression (a view the user holds, or another consumer, needs the window
    itself). Every other store and read keeps `setitem_op` / `getitem_op`.

    Of two lowerings of such a store the kernel is taken where the program
    runs on a TPU and `_kernel_plan` accepts the store; everywhere else the
    select is."""
    spec = lin.spec
    avals = [node.aval for node in lin.nodes]

    def shape_of(ref):
        kind, v = ref
        if kind == _REF_STATIC:
            return None
        return tuple(avals[v].shape if kind == _REF_NODE else lin.leaves[v].shape)

    def expression(root, full, origin, sizes):
        """The nodes of a store's value, each with its shift (a read) or None
        (an operator), and how often the expression reads each; None where
        something in it does not qualify."""
        tree: dict[int, tuple | None] = {}
        read_here = {root: 1}
        stack = [root]
        while stack:
            i = stack.pop()
            if i in tree:
                continue
            fn, refs, _ = spec[i]
            if fn is getitem_op:
                read = _window(refs[1][1], full) if shape_of(refs[0]) == full else None
                if read is None or read[1] != sizes:
                    return None
                tree[i] = tuple(r - o for r, o in zip(read[0], origin))
            elif any(fn is op for op in ELEMENTWISE_OPS):
                tree[i] = None
                for kind, v in refs:
                    if kind == _REF_STATIC:
                        if not isinstance(v, _SCALARS):
                            return None
                    elif shape_of((kind, v)) != ():  # (a 0-d array is computed as ever)
                        if kind != _REF_NODE:
                            return None
                        read_here[v] = read_here.get(v, 0) + 1
                        stack.append(v)
            else:
                return None
        return tree, read_here

    readers = list(lin.node_refs)
    for i in out_indices:
        readers[i] += 1  # whoever takes the output reads the window itself

    shifts: dict[int, tuple] = {}
    stores: dict[int, tuple] = {}
    kernels: dict[int, _Kernel] = {}
    for s, (fn, refs, _) in enumerate(spec):
        if fn is not setitem_op or refs[1][0] != _REF_NODE:
            continue
        full, root = tuple(avals[s].shape), refs[1][1]
        window = _window(refs[2][1], full)
        if window is None:
            continue
        origin, sizes = window
        if (2 * math.prod(sizes) < math.prod(full) or tuple(avals[root].shape) != sizes
                or avals[root].dtype != avals[s].dtype):
            continue
        found = expression(root, full, origin, sizes)
        if found is None:
            continue
        tree, read_here = found
        if all(readers[i] == read_here[i] for i in tree):
            shifts.update((i, shift) for i, shift in tree.items() if shift is not None)
            stores[s] = window
            kernel = _kernel_plan(lin, s, tree) if platform == "tpu" else None
            if kernel is not None:
                kernels[s] = kernel
    return shifts, stores, kernels


# The contractions: what `np.dot`, `np.matmul` and `@`, `np.inner`,
# `np.tensordot` and `np.einsum` call on the device. On a TPU they are the
# work of the MXU, at `MATMUL_PRECISION`.
DOT_OPS = (jnp.dot, jnp.matmul, jnp.inner, jnp.tensordot, jnp.einsum)


def _eqns(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)  # a ClosedJaxpr holds its Jaxpr
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def _dot_flops(fn, refs, kwargs, aval_of) -> int:
    """The floating-point operations of one contraction node, from shapes
    alone: for each `dot_general` that `fn` is over operands of these shapes,
    2 x the elements of its result x the product of the contracted dimensions
    (a multiplication and an addition for each). What any execution does,
    whatever the passes the chip makes of each multiplication."""
    def call(*arrays):
        given = iter(arrays)
        return fn(*[v if kind == _REF_STATIC else next(given) for kind, v in refs], **kwargs)

    operands = [aval_of(ref) for ref in refs if ref[0] != _REF_STATIC]
    closed = jax.make_jaxpr(call)(*[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in operands])
    flops = 0
    for eqn in _eqns(closed.jaxpr):
        if eqn.primitive.name == "dot_general":
            (contracted, _), _ = eqn.params["dimension_numbers"]
            left = eqn.invars[0].aval.shape
            flops += 2 * math.prod(eqn.outvars[0].aval.shape) * math.prod(left[d] for d in contracted)
    return flops


def _census(lin: _Linear) -> tuple[int, int, int]:
    """(contraction nodes, their operations, ufunc-method nodes) of one
    execution of `lin`'s program. A ufunc's method is a node whose function
    is a bound method of a `jnp.ufunc`, or `at_op` (`shim._UfuncDispatcher`)."""
    def aval_of(ref):
        kind, v = ref
        return lin.nodes[v].aval if kind == _REF_NODE else lin.leaves[v]

    dots = [(fn, refs, kwargs) for fn, refs, kwargs in lin.spec if any(fn is op for op in DOT_OPS)]
    methods = sum(1 for fn, _, _ in lin.spec
                  if fn is at_op or isinstance(getattr(fn, "__self__", None), jnp.ufunc))
    return len(dots), sum(_dot_flops(*dot, aval_of) for dot in dots), methods


# The kernel's builder, under a name of this module: a test on the CPU puts
# `functools.partial(stencil.window_store, interpret=True)` here.
window_store = stencil.window_store


def _shifted(arr, shift):
    """`out[i] = arr[i + shift]` at arr's own shape, zero where that leaves
    it: one `lax.pad` with negative edges, which XLA keeps inside the fusion
    that reads it (a slice and a pad, or a roll, it materializes first)."""
    if not any(shift):
        return arr
    return jax.lax.pad(arr, jnp.zeros((), arr.dtype), [(-d, d, 0) for d in shift])


def _select_window(arr, value, starts, sizes):
    """`arr` with `value` inside the window, both at arr's shape: a select
    against the window's mask, so that the output can alias `arr`."""
    mask = None
    for axis, (n, start, size) in enumerate(zip(arr.shape, starts, sizes)):
        if size == n:
            continue
        at = jax.lax.iota(jnp.int32, n)
        inside = ((at >= start) & (at < start + size)).reshape(
            [n if k == axis else 1 for k in range(arr.ndim)])
        mask = inside if mask is None else mask & inside
    return value if mask is None else jnp.where(mask, value, arr)


def _make_runner(spec, out_indices, shifts, stores, kernels):
    in_a_kernel = {i for kernel in kernels.values() for i in kernel.nodes}

    def run(*leaves):
        vals = []

        def value_of(ref):
            kind, v = ref
            return vals[v] if kind == _REF_NODE else leaves[v]

        for i, (fn, refs, kwargs) in enumerate(spec):
            if i in in_a_kernel:  # evaluated strip by strip, inside its store's kernel
                vals.append(None)
                continue
            if i in kernels:
                kernel = kernels[i]
                vals.append(window_store(
                    value_of(kernel.target), [value_of(ref) for ref in kernel.sources], kernel.reads,
                    [value_of(ref) for ref in kernel.scalars], kernel.expression, *stores[i],
                    kernel.block_rows, target_read=kernel.expression.reads_target))
                continue
            args = [v if kind == _REF_STATIC else value_of((kind, v)) for kind, v in refs]
            if i in shifts:
                vals.append(_shifted(args[0], shifts[i]))
            elif i in stores:
                vals.append(_select_window(args[0], args[1], *stores[i]))
            else:
                # (an operator between shifted reads runs at the full shape)
                vals.append(fn(*args, **kwargs))
        return tuple(vals[i] for i in out_indices)

    return run


def materialize(root: Node) -> jax.Array:
    return materialize_all([root])[0]


def materialize_all(roots: list[Node]) -> list[jax.Array]:
    """Compute `roots` in one program and return their values, in order."""
    with counters:
        if any(root.value is None for root in roots):
            _run([root for root in roots if root.value is None])
        return [root.value for root in roots]


def _platform(leaves) -> str:
    """Where a program over `leaves` runs: the platform of the one device each
    of its arrays lives on, jax's default where it has none (a program that
    creates its arrays), "" where they disagree or one is sharded."""
    platforms = set()
    for leaf in leaves:
        if isinstance(leaf, jax.Array):
            devices = leaf.devices()
            if len(devices) != 1:
                return ""
            platforms.update(d.platform for d in devices)
    if not platforms:
        return jax.default_backend()
    return platforms.pop() if len(platforms) == 1 else ""


def _run(roots: list[Node]) -> None:
    lin = _Linear(roots)
    # Which values come back shapes the compiled output tuple, and which
    # leaves are donated its aliases, so both are part of the cache key.
    out_indices = _outputs(lin, roots)
    donated = _donatable(lin, out_indices)
    key = (lin.key, tuple(out_indices), tuple(donated))

    def build(platform: str) -> _Program:
        shifts, stores, kernels = _full_shape_plan(lin, out_indices, platform)
        returned = _paired(lin, out_indices, donated)
        runner = jax.jit(_make_runner(lin.spec, returned, shifts, stores, kernels),
                         donate_argnums=tuple(donated))
        _exec_cache[key] = _Program(runner, returned, len(stores), len(kernels), *_census(lin))
        return _exec_cache[key]

    program = _exec_cache.get(key)
    if program is None:
        if len(_exec_cache) >= _CACHE_LIMIT:
            _exec_cache.clear()
        counters.exec_cache_misses += 1
        program = build(_platform(lin.leaves))
    leaves = []
    for leaf in lin.leaves:
        leaves.append(leaf if isinstance(leaf, jax.Array) else ship(leaf))
    counters.programs += 1
    counters.nodes += len(lin.spec)
    counters.donated_bytes += sum(leaves[li].nbytes for li in donated)
    counters.aligned_stores += program.aligned_stores
    counters.dots += program.dots
    counters.dot_flops += program.dot_flops
    counters.ufunc_methods += program.ufunc_methods
    called = time.perf_counter()
    with jax.profiler.TraceAnnotation("shim.materialize"), precision_scope():
        try:
            outs = program.runner(*leaves)
        except Exception:  # noqa: BLE001 — whatever a kernel's build or the chip's compiler raised
            if not program.kernel_stores or any(leaf.is_deleted() for leaf in leaves):
                raise
            # A kernel never fails a turn: the program again with every store
            # as the select it was, from now on (an error of the program's own
            # comes back from that one too).
            logger.warning("a window store's kernel was refused; the select form runs", exc_info=True)
            program = build("")
            outs = program.runner(*leaves)
    counters.kernel_stores += program.kernel_stores
    counters.spent("dispatch_s", called)
    for i, value in zip(program.returned, outs):
        node = lin.nodes[i]
        for owner in node.live_owners():
            owner._concrete = value
            owner._node = None
        node.value, node.arg_refs, node.n_nodes = value, [], 1


# --------------------------------------------------------------------------
# Op registry helpers used by the shim layer.

# Op helpers. IMPORTANT: statics (indices, dtypes, shapes) must be passed as
# ARGUMENTS, never captured in closures — only arguments enter the structure
# key, and a cached runner is reused for any graph with an equal key.

def getitem_op(arr, idx):
    return arr[idx]


def setitem_op(arr, value, idx):
    return arr.at[idx].set(value)


def at_op(arr, value, idx, update):
    """`np.<ufunc>.at(arr, idx, value)` as a functional update (`update`: the
    indexed update's name, `add`, `max`, ...); repeated indices accumulate."""
    return getattr(arr.at[idx], update)(value)


def astype_op(arr, dtype):
    return arr.astype(dtype)


def reshape_op(arr, shape):
    return jnp.reshape(arr, shape)


def iota_op(shape, dtype, axis):
    return jax.lax.broadcasted_iota(dtype, shape, axis)


def indices_op(dimensions, dtype):
    return jnp.indices(dimensions, dtype=dtype)


def random_uniform_op(key, shape):
    return jax.random.uniform(key, shape)


def random_normal_op(key, shape):
    return jax.random.normal(key, shape)
