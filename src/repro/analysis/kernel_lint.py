"""Layer 2: Pallas launch-geometry lint.

``pl.pallas_call`` is monkeypatched with a recording spy and the
kernel suite's public entry points (``kernels/ops.py``) are traced
under ``jax.eval_shape`` over a representative workload sweep —
training-shaped, decode-shaped, fp8 and paged launches.  Nothing
executes; we only capture each launch's grid, Block/scratch specs and
operand avals, then apply the geometry rules:

* **KL001** — a block dim strictly larger than its operand extent
  (the PR-6 oversize-tile bug class, generalized past `_check_tiles`).
* **KL002** — ``grid`` x ``index_map`` does not cover the output
  extent (rows silently never written).
* **KL003/KL004** — lane/sublane misalignment: last block dim not a
  multiple of 128, second-minor not a multiple of 8.  A block dim
  equal to the full operand extent is exempt (nothing to realign) —
  that keeps auto-fitted decode tiles clean while still flagging an
  explicit 96-wide training tile.
* **KL005** — estimated VMEM working set (all VMEM blocks + VMEM
  scratch) over the per-core budget.

Specs with ``memory_space=ANY`` (HBM-resident pools) have no block
shape and are skipped; ``PrefetchScalarGridSpec`` index maps take
scalar-prefetch refs we cannot substitute statically, so KL002 skips
launches whose index maps are not pure grid functions.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import contracts
from repro.analysis.report import Finding, Report


@dataclasses.dataclass
class Launch:
    """One captured ``pallas_call`` invocation."""
    kernel: str                      # kernel function name
    module: str                      # defining module
    workload: str                    # which sweep entry triggered it
    grid: Optional[Tuple[int, ...]]
    in_specs: List[Any]              # BlockSpecs (or None)
    out_specs: List[Any]
    out_shapes: List[Any]            # ShapeDtypeStructs
    scratch_shapes: List[Any]
    num_scalar_prefetch: int
    operands: List[Tuple[Tuple[int, ...], Any]]   # (shape, dtype)

    def label(self) -> str:
        return f"{self.module}.{self.kernel} [{self.workload}]"


def _unwrap(fn: Callable) -> Callable:
    while isinstance(fn, functools.partial):
        fn = fn.func
    return fn


def _as_list(x) -> list:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _record(workload: str, kernel: Callable, args: tuple, kw: dict,
            operands: tuple) -> Launch:
    out_shape = kw.get("out_shape", args[0] if args else None)
    grid_spec = kw.get("grid_spec")
    if grid_spec is not None:
        grid = tuple(grid_spec.grid or ())
        in_specs = _as_list(grid_spec.in_specs)
        out_specs = _as_list(grid_spec.out_specs)
        scratch = _as_list(getattr(grid_spec, "scratch_shapes", None))
        npf = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
    else:
        g = kw.get("grid")
        grid = tuple(g) if g is not None else None
        in_specs = _as_list(kw.get("in_specs"))
        out_specs = _as_list(kw.get("out_specs"))
        scratch = _as_list(kw.get("scratch_shapes"))
        npf = 0
    fn = _unwrap(kernel)
    return Launch(
        kernel=getattr(fn, "__name__", str(fn)),
        module=getattr(fn, "__module__", "?"),
        workload=workload,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shapes=_as_list(out_shape),
        scratch_shapes=scratch,
        num_scalar_prefetch=npf,
        operands=[(tuple(getattr(o, "shape", ())),
                   getattr(o, "dtype", None)) for o in operands])


@contextlib.contextmanager
def capture_launches(records: List[Launch], workload: str = "inline"):
    """Swap ``pl.pallas_call`` for a spy that records each launch's
    geometry at *invoke* time (operand avals included), then runs the
    real launch."""
    import jax.experimental.pallas as pl_mod
    real = pl_mod.pallas_call

    def spy(kernel, *args, **kw):
        inner = real(kernel, *args, **kw)

        def wrapped(*operands):
            records.append(_record(workload, kernel, args, kw, operands))
            return inner(*operands)
        return wrapped

    pl_mod.pallas_call = spy
    try:
        yield records
    finally:
        pl_mod.pallas_call = real


# ----------------------------------------------------------------------
# geometry checks
# ----------------------------------------------------------------------

def _block_pairs(launch: Launch):
    """Yield (role, spec, operand_shape, dtype) for every spec with a
    concrete block shape, input and output."""
    ops = launch.operands[launch.num_scalar_prefetch:]
    for i, spec in enumerate(launch.in_specs):
        bs = getattr(spec, "block_shape", None)
        if bs is None or i >= len(ops):
            continue
        shape, dtype = ops[i]
        yield f"in[{i}]", tuple(bs), shape, dtype
    for i, spec in enumerate(launch.out_specs):
        bs = getattr(spec, "block_shape", None)
        if bs is None or i >= len(launch.out_shapes):
            continue
        o = launch.out_shapes[i]
        yield f"out[{i}]", tuple(bs), tuple(o.shape), o.dtype


def _concrete(block, shape):
    """Block dims with None entries resolved to the full extent."""
    return tuple(shape[i] if b is None else int(b)
                 for i, b in enumerate(block))


def _check_geometry(launch: Launch, report: Report) -> None:
    vmem_bytes = 0
    for role, block, shape, dtype in _block_pairs(launch):
        if len(block) != len(shape):
            continue     # unblocked/collapsed spec; nothing to audit
        cb = _concrete(block, shape)
        for d, (b, s) in enumerate(zip(cb, shape)):
            if b > s:
                report.add(Finding(
                    "KL001",
                    f"{launch.label()}: {role} block {cb} exceeds "
                    f"operand extent {shape} in dim {d}",
                    detail={"launch": launch.label(), "role": role,
                            "block": list(cb), "shape": list(shape)}))
        if len(cb) >= 1:
            b, s = cb[-1], shape[-1]
            if b != s and b % contracts.LANE:
                report.add(Finding(
                    "KL003",
                    f"{launch.label()}: {role} last block dim {b} is "
                    f"neither a multiple of {contracts.LANE} nor the "
                    f"full extent {s}",
                    detail={"launch": launch.label(), "role": role,
                            "block": list(cb), "shape": list(shape)}))
        if len(cb) >= 2:
            b, s = cb[-2], shape[-2]
            # b == 1 is the grid-mapped-axis pattern (one row/batch
            # element per cell), not a packing decision — exempt
            if b not in (1, s) and b % contracts.SUBLANE:
                report.add(Finding(
                    "KL004",
                    f"{launch.label()}: {role} second-minor block dim "
                    f"{b} is neither a multiple of {contracts.SUBLANE} "
                    f"nor the full extent {s}",
                    detail={"launch": launch.label(), "role": role,
                            "block": list(cb), "shape": list(shape)}))
        if dtype is not None:
            vmem_bytes += math.prod(cb) * jnp.dtype(dtype).itemsize

    for s in launch.scratch_shapes:
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", None)
        if shape is None or dtype is None:
            continue     # semaphores etc.
        try:
            vmem_bytes += math.prod(tuple(shape)) \
                * jnp.dtype(dtype).itemsize
        except TypeError:
            continue

    if vmem_bytes > contracts.VMEM_BUDGET_BYTES:
        report.add(Finding(
            "KL005",
            f"{launch.label()}: estimated VMEM working set "
            f"{vmem_bytes} B exceeds the "
            f"{contracts.VMEM_BUDGET_BYTES} B budget",
            detail={"launch": launch.label(), "bytes": vmem_bytes}))


def _check_coverage(launch: Launch, report: Report) -> None:
    """KL002: the output index maps, evaluated over the whole grid,
    must hit every output block."""
    grid = launch.grid
    if not grid:
        return
    cells = math.prod(grid)
    if cells > contracts.GRID_EVAL_CAP:
        return
    for i, spec in enumerate(launch.out_specs):
        bs = getattr(spec, "block_shape", None)
        imap = getattr(spec, "index_map", None)
        if bs is None or imap is None or i >= len(launch.out_shapes):
            continue
        shape = tuple(launch.out_shapes[i].shape)
        if len(bs) != len(shape):
            continue
        cb = _concrete(tuple(bs), shape)
        if any(b <= 0 for b in cb):
            continue
        needed_axes = [range(-(-s // b)) for s, b in zip(shape, cb)]
        if math.prod(len(r) for r in needed_axes) > contracts.GRID_EVAL_CAP:
            continue
        covered = set()
        try:
            for cell in itertools.product(*(range(g) for g in grid)):
                idx = imap(*cell)
                covered.add(tuple(int(x) for x in idx))
        except Exception:
            continue     # index map needs scalar-prefetch refs
        missing = [t for t in itertools.product(*needed_axes)
                   if t not in covered]
        if missing:
            report.add(Finding(
                "KL002",
                f"{launch.label()}: grid {grid} never writes output "
                f"block(s) {missing[:4]}{'...' if len(missing) > 4 else ''} "
                f"of out[{i}] {shape} / block {cb}",
                detail={"launch": launch.label(), "out": i,
                        "missing": [list(m) for m in missing[:16]],
                        "grid": list(grid)}))


def check_launches(records: Sequence[Launch], report: Report) -> None:
    for launch in records:
        _check_geometry(launch, report)
        _check_coverage(launch, report)


# ----------------------------------------------------------------------
# default workload sweep over kernels/ops.py
# ----------------------------------------------------------------------

def _sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def default_workloads() -> List[Tuple[str, Callable[[], Any]]]:
    """(name, thunk) pairs; each thunk abstractly evaluates one public
    kernel entry point.  ``__wrapped__`` bypasses the jit cache so the
    trace (and the spy) always runs."""
    from repro.kernels import ops
    bf16, i32 = jnp.bfloat16, jnp.int32
    e4m3 = jnp.float8_e4m3fn

    def raw(fn):
        return getattr(fn, "__wrapped__", fn)

    def ev(fn, *args, **kw):
        return lambda: jax.eval_shape(functools.partial(raw(fn), **kw),
                                      *args)

    def fp8_scaled(fn):
        # the scale pools are arrays the wrapper indexes, so they are
        # traced positionally rather than bound as keywords
        return lambda q, k, v, bt, n, ks, vs: fn(q, k, v, bt, n,
                                                 k_scale=ks, v_scale=vs)

    B, KH, G, hd, bs, NB, MB, C = 2, 2, 2, 64, 16, 8, 4, 16
    return [
        ("matmul_train_auto",
         ev(ops.matmul, _sds(256, 256, dtype=bf16),
            _sds(256, 256, dtype=bf16))),
        ("matmul_explicit_128",
         ev(ops.matmul, _sds(128, 128), _sds(128, 128),
            bm=128, bn=128, bk=128)),
        ("matmul_decode_rows",
         ev(ops.matmul, _sds(8, 256, dtype=bf16),
            _sds(256, 128, dtype=bf16))),
        ("fp8_matmul_256",
         ev(ops.fp8_matmul, _sds(256, 256, dtype=e4m3),
            _sds(256, 256, dtype=e4m3), _sds(), _sds())),
        ("flash_attention_train",
         ev(ops.flash_attention, _sds(2, 256, 4, 64, dtype=bf16),
            _sds(2, 256, 4, 64, dtype=bf16),
            _sds(2, 256, 4, 64, dtype=bf16), causal=True)),
        ("flash_attention_short",
         ev(ops.flash_attention, _sds(2, 8, 4, 64, dtype=bf16),
            _sds(2, 8, 4, 64, dtype=bf16),
            _sds(2, 8, 4, 64, dtype=bf16), causal=True)),
        ("tropical_matmul_128",
         ev(ops.tropical_matmul, _sds(128, 128, dtype=i32),
            _sds(128, 128, dtype=i32), bm=128, bn=128, bk=128)),
        ("smith_waterman",
         ev(ops.smith_waterman, _sds(2, 64, dtype=i32),
            _sds(2, 64, dtype=i32))),
        ("pipelined_matmul_128",
         ev(ops.pipelined_matmul, _sds(128, 128), _sds(128, 128),
            bm=128, bn=128, bk=128)),
        ("paged_decode",
         ev(ops.paged_decode_attention,
            _sds(B, 1, KH * G, hd, dtype=bf16),
            _sds(NB, KH, bs, hd, dtype=bf16),
            _sds(NB, KH, bs, hd, dtype=bf16),
            _sds(B, MB, dtype=i32), _sds(B, dtype=i32))),
        ("paged_decode_fp8",
         ev(fp8_scaled(ops.paged_decode_attention),
            _sds(B, 1, KH * G, hd, dtype=bf16),
            _sds(NB, KH, bs, hd, dtype=e4m3),
            _sds(NB, KH, bs, hd, dtype=e4m3),
            _sds(B, MB, dtype=i32), _sds(B, dtype=i32),
            _sds(NB, KH, bs), _sds(NB, KH, bs))),
        ("paged_chunk",
         ev(ops.paged_chunk_attention,
            _sds(B, C, KH * G, hd, dtype=bf16),
            _sds(NB, KH, bs, hd, dtype=bf16),
            _sds(NB, KH, bs, hd, dtype=bf16),
            _sds(B, MB, dtype=i32), _sds(B, dtype=i32))),
        ("paged_chunk_fp8",
         ev(fp8_scaled(ops.paged_chunk_attention),
            _sds(B, C, KH * G, hd, dtype=bf16),
            _sds(NB, KH, bs, hd, dtype=e4m3),
            _sds(NB, KH, bs, hd, dtype=e4m3),
            _sds(B, MB, dtype=i32), _sds(B, dtype=i32),
            _sds(NB, KH, bs), _sds(NB, KH, bs))),
    ]


def run(report: Report,
        workloads: Optional[List[Tuple[str, Callable]]] = None) -> None:
    workloads = default_workloads() if workloads is None else workloads
    records: List[Launch] = []
    for name, thunk in workloads:
        with capture_launches(records, workload=name):
            thunk()
    report.extras.setdefault("kernel_launches", []).extend(
        launch.label() for launch in records)
    check_launches(records, report)
