"""Device mesh management: the framework's parallelism substrate.

The reference's parallelism is Spark partitions + sockets (SURVEY.md §2.10);
here every distributed computation runs SPMD over a `jax.sharding.Mesh` with
named axes:

    data    — batch/data parallel (the reference's mapPartitions analog)
    model   — tensor parallel (reserved; reference has none)
    seq     — sequence/context parallel for long inputs (ring attention)

XLA inserts the collectives (psum/all_gather/reduce_scatter) from sharding
annotations; they ride ICI within a slice and DCN across slices.
"""
from __future__ import annotations

import contextlib
import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map  # re-exported: the package's one import site
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "MESH_AXIS_NAMES",
    "make_mesh",
    "MeshPlan",
    "default_mesh",
    "target_devices",
    "MeshContext",
    "batch_sharding",
    "replicated_sharding",
    "addressable_shard_layout",
    "host_device_groups",
    "shard_batch",
    "shard_map",
    "pad_to_multiple",
    "collectives_by_loop",
]

# Every axis name a mesh in this codebase may declare.  graftlint G501
# (né G305) checks any axis literal inside a PartitionSpec — and any
# collective's axis_name — against this tuple (a typo'd axis name does
# not error — XLA silently replicates the leaf), and
# sharding_rules.validate_rules does the same at runtime.  Keep it a
# plain tuple literal: the lint parses it via AST without importing jax.
MESH_AXIS_NAMES = ("data", "model", "seq", "pipe")

_CURRENT: Dict[str, Optional[Mesh]] = {"mesh": None}


def make_mesh(
    data: int = -1,
    model: int = 1,
    seq: int = 1,
    devices: Optional[Sequence] = None,
    dcn_data: int = 1,
) -> Mesh:
    """Build a (data, model, seq) mesh.  `data=-1` absorbs remaining devices.

    `dcn_data` > 1 declares a multi-slice layout: the data axis's leading
    `dcn_data` blocks each live on one slice, so only data-parallel
    collectives cross DCN while model/seq collectives stay on ICI (the
    scaling-book slice layout; placement comes from
    utils.cluster.device_topology rather than raw device order).  When the
    runtime reports fewer slices than requested (the virtual CPU test
    mesh), devices are grouped into `dcn_data` contiguous virtual slices so
    the layout still compiles and is exercised by tests/dryruns.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if data == -1:
        if n % (model * seq) != 0:
            raise ValueError(f"{n} devices not divisible by model*seq={model * seq}")
        data = n // (model * seq)
    if data * model * seq != n:
        raise ValueError(f"mesh {data}x{model}x{seq} != {n} devices")
    if dcn_data > 1:
        from ..utils.cluster import device_topology

        if data % dcn_data != 0:
            raise ValueError(f"data={data} not divisible by dcn_data={dcn_data}")
        topo = device_topology(devices)
        if topo.num_slices == dcn_data:
            groups = topo.slice_groups()
        elif topo.num_slices <= 1:
            # single-slice / virtual runtimes (the CPU test mesh): contiguous
            # equal groups emulate slices so the layout still compiles
            per = n // dcn_data
            groups = [list(range(g * per, (g + 1) * per))
                      for g in range(dcn_data)]
        else:
            # a real multi-slice job with a mismatched request must not be
            # silently laid out across slice boundaries
            raise ValueError(
                f"dcn_data={dcn_data} does not match the runtime's "
                f"{topo.num_slices} slices")
        if len({len(g) for g in groups}) != 1:
            raise ValueError("unequal slice sizes cannot form a mesh")
        # slice-major ordering puts the DCN boundary on the leading blocks
        # of the data axis
        ordered = [devices[i] for g in groups for i in g]
        arr = np.asarray(ordered).reshape(data, model, seq)
    else:
        arr = np.asarray(devices).reshape(data, model, seq)
    return Mesh(arr, axis_names=("data", "model", "seq"))


class MeshPlan:
    """One (data, model, pipe) layout for the 3D-mesh GSPMD trainer:
    D-way data parallelism x T-way megatron tensor parallelism x P-way
    GPipe pipeline parallelism on a SINGLE mesh, so XLA composes all
    three collective families in one program (the make_lm_train_step_3d
    substrate; docs/performance.md "The 3D mesh").

    ``data=-1`` absorbs the remaining devices.  The axis names are the
    plan's contract with every partition-rule table — `validate_specs`
    is the runtime check graftlint G501 (né G305) performs statically."""

    AXES = ("data", "model", "pipe")

    def __init__(self, data: int = -1, model: int = 1, pipe: int = 1,
                 devices: Optional[Sequence] = None):
        devices = list(devices if devices is not None else jax.devices())
        n = len(devices)
        if model < 1 or pipe < 1:
            raise ValueError(f"model={model} and pipe={pipe} must be >= 1")
        if data == -1:
            if n % (model * pipe) != 0:
                raise ValueError(
                    f"{n} devices not divisible by model*pipe="
                    f"{model * pipe}")
            data = n // (model * pipe)
        if data * model * pipe != n:
            raise ValueError(
                f"mesh plan {data}x{model}x{pipe} != {n} devices")
        self.data, self.model, self.pipe = int(data), int(model), int(pipe)
        arr = np.asarray(devices).reshape(self.data, self.model, self.pipe)
        self.mesh = Mesh(arr, axis_names=self.AXES)

    @property
    def shape(self) -> Dict[str, int]:
        return dict(self.mesh.shape)

    def validate_specs(self, rules) -> None:
        """Raise if any rule's spec names an axis this plan's mesh does
        not declare (the silent-full-replication typo G501 catches in
        source)."""
        from .sharding_rules import validate_rules

        validate_rules(rules, self.AXES)

    def __repr__(self) -> str:
        return (f"MeshPlan(data={self.data}, model={self.model}, "
                f"pipe={self.pipe})")


def default_mesh() -> Mesh:
    """The ambient mesh: explicitly-entered MeshContext, else all devices on
    the data axis."""
    if _CURRENT["mesh"] is not None:
        return _CURRENT["mesh"]
    return make_mesh()


def target_devices(mesh: Optional[Mesh] = None) -> List:
    """The devices the computation being traced will run on — THE rule
    every kernel-vs-XLA dispatch keys on (ops.pallas_kernels.on_tpu /
    on_single_tpu): the explicit `mesh`'s devices, else
    the entered MeshContext's, else every device of the default backend
    (what `default_mesh()` lays the data axis over).  A one-device
    computation on a multi-chip host therefore declares itself with
    ``MeshContext(make_mesh(devices=[dev]))`` and keeps its kernels; the
    global `jax.device_count()` never decides."""
    mesh = mesh if mesh is not None else _CURRENT["mesh"]
    return list(mesh.devices.flat) if mesh is not None else jax.devices()


@contextlib.contextmanager
def MeshContext(mesh: Mesh):
    prev = _CURRENT["mesh"]
    _CURRENT["mesh"] = mesh
    try:
        with mesh:
            yield mesh
    finally:
        _CURRENT["mesh"] = prev


def batch_sharding(mesh: Mesh, ndim: int = 1, batch_axis: int = 0) -> NamedSharding:
    """Shard the batch axis over 'data'; everything else replicated."""
    spec = [None] * ndim
    spec[batch_axis] = "data"
    return NamedSharding(mesh, P(*spec))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def addressable_shard_layout(sharding, shape):
    """[(device, index)] for every addressable shard of `shape` under
    `sharding`, in stable device-id order — or None when the shape does
    not divide evenly (callers fall back to one coalesced transfer).

    This is the substrate of the sharded direct-to-chip path
    (io/shard_put.py): each (device, index) pair becomes ONE
    `jax.device_put(arr[index], device)` riding its own transfer stream,
    and the shards reassemble zero-copy with
    `jax.make_array_from_single_device_arrays`."""
    try:
        imap = sharding.addressable_devices_indices_map(tuple(shape))
    except (ValueError, TypeError):
        return None
    if not imap or any(idx is None for idx in imap.values()):
        return None
    return sorted(imap.items(), key=lambda di: di[0].id)


def host_device_groups(devices, num_hosts: int):
    """Partition `devices` into `num_hosts` equal contiguous groups — the
    simulated-host layout elastic tests and soaks use on the forced
    virtual CPU mesh (tools/dist_soak.py leg A treats 8 devices as
    4 hosts x 2 chips).  A real pod never calls this: per-process
    addressability already partitions the device set, and
    `addressable_shard_layout` above is per-host by construction."""
    devices = list(devices)
    n = len(devices)
    if num_hosts < 1 or n % num_hosts != 0:
        raise ValueError(
            f"{n} devices do not split into {num_hosts} equal hosts")
    per = n // num_hosts
    return [devices[i * per:(i + 1) * per] for i in range(num_hosts)]


def pad_to_multiple(arr: np.ndarray, multiple: int, axis: int = 0) -> Tuple[np.ndarray, int]:
    """Pad `axis` up to a multiple (static shapes for XLA; padded rows are
    dropped after unbatching).  Returns (padded, original_len)."""
    n = arr.shape[axis]
    target = math.ceil(max(n, 1) / multiple) * multiple
    if target == n:
        return arr, n
    pad_width = [(0, 0)] * arr.ndim
    pad_width[axis] = (0, target - n)
    return np.pad(arr, pad_width, mode="edge"), n


def shard_batch(arr: np.ndarray, mesh: Optional[Mesh] = None) -> Tuple[jax.Array, int]:
    """Pad the leading axis to the data-parallel degree and device_put with a
    batch sharding — the device-feed path replacing the reference's
    mapPartitions dispatch (CNTKModel.scala:526-531).
    """
    mesh = mesh or default_mesh()
    dp = mesh.shape["data"]
    padded, n = pad_to_multiple(np.asarray(arr), dp, axis=0)
    out = jax.device_put(padded, batch_sharding(mesh, padded.ndim))
    return out, n


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) "
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_HLO_CALLEES = re.compile(
    r"\b(condition|body|to_apply|calls|true_computation|false_computation"
    r"|branch_computations)=(%?[\w.\-]+|\{[^}]*\})")
_HLO_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_HLO_GROUPS = re.compile(
    r"(?:replica_groups|source_target_pairs)="
    r"(\{(?:\{[\d,]*\},?)*\}|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")


def _hlo_result_bytes(result: str, kind: str, start: bool) -> int:
    arrays = _HLO_ARRAY.findall(result)
    if start and kind in ("all-gather", "collective-permute"):
        # (operands, results, u32 contexts): the results are what moves
        arrays = [a for a in arrays if a != ("u32", "")]
        arrays = arrays[len(arrays) // 2:]
    total = 0
    for dtype, dims in arrays:
        width = 8 if dtype == "pred" else int(re.sub(r"\D", "", dtype) or 0)
        total += math.prod(int(d) for d in dims.split(",") if d) * width // 8
    return total


def _hlo_groups(text: str) -> List[List[int]]:
    """Replica groups (or a permute's pairs) as lists of device ids, from
    either spelling: `{{0,1},{2,3}}` or the iota form `[2,2]<=[2,2]T(1,0)`
    (arange over the second shape, transposed, reshaped to the first)."""
    if text.startswith("{"):
        return [[int(i) for i in g.split(",") if i]
                for g in re.findall(r"\{([\d,]*)\}", text[1:-1])]
    shape, dims, perm = re.match(
        r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", text).groups()
    dims = [int(d) for d in dims.split(",")]
    ids = np.arange(math.prod(dims)).reshape(dims)
    if perm:
        ids = ids.transpose([int(d) for d in perm.split(",")])
    return ids.reshape([int(d) for d in shape.split(",")]).tolist()


def collectives_by_loop(compiled, mesh: Optional[Mesh] = None) -> List[dict]:
    """What a compiled program sends between devices, and how often: one
    record for every collective in the optimized HLO of `compiled` (a
    `jax.stages.Compiled`, or its `as_text()`), in program order:

        {"name", "kind", "bytes", "groups", "axes", "loops"}

    `kind` is all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute (an async `-start` counts, its `-done` does not);
    `bytes` is the size of the result on one device; `groups` the replica
    groups (a permute's source-target pairs) as lists of device ids;
    `loops` the number of `while` loops the instruction sits in (0 = once
    a call, 1 = once an iteration of an outer scan, ...).  With the
    program's `mesh`, `axes` names the mesh axes a group spans (ids index
    `mesh.devices.flat`, jit's device assignment); without one it is None.

    A static property of the program, read from text: the same answer on
    the CPU test mesh and from a described-TPU compile.  It is how
    tests/test_trainer3d.py holds `make_lm_train_step_3d` to ONE
    `data`-axis all-reduce of the gradients a step, outside every loop."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    found, homes, calls, entry, here = [], [], {}, None, None
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            here = head.group(1)
            calls[here] = []
            if line.startswith("ENTRY"):
                entry = here
            continue
        if here is None or " = " not in line:
            continue
        in_while = re.search(r"\bwhile\(", line) is not None
        for role, names in _HLO_CALLEES.findall(line):
            for name in names.strip("{}").split(", "):
                calls[here].append((name.lstrip("%"), int(
                    in_while and role in ("body", "condition"))))
        op = _HLO_COLLECTIVE.match(line)
        if not op:
            continue
        name, result, kind, start = op.groups()
        groups = _HLO_GROUPS.search(line)
        found.append({"name": name, "kind": kind,
                      "bytes": _hlo_result_bytes(result, kind, bool(start)),
                      "groups": _hlo_groups(groups.group(1)) if groups
                      else []})
        homes.append(here)
    # a computation's depth is the deepest chain of while bodies that
    # reaches it from the entry (fusions, calls and branches add none)
    loops = {entry: 0}
    todo = [entry]
    while todo:
        comp = todo.pop()
        for callee, step in calls.get(comp, ()):
            if loops.get(callee, -1) < loops[comp] + step:
                loops[callee] = loops[comp] + step
                todo.append(callee)
    for rec, home in zip(found, homes):
        rec["loops"] = loops.get(home, 0)
        rec["axes"] = None if mesh is None else _axes_spanned(
            rec["groups"], mesh)
    return found


def _axes_spanned(groups: List[List[int]], mesh: Mesh) -> Tuple[str, ...]:
    """The mesh axes along which the members of a group differ; no groups
    at all is HLO's spelling of one group of every device."""
    shape = mesh.devices.shape
    spans = set()
    for g in groups or [list(range(math.prod(shape)))]:
        coords = np.unravel_index(np.asarray(g, int), shape)
        spans.update(name for name, c in zip(mesh.axis_names, coords)
                     if len(set(c.tolist())) > 1)
    return tuple(a for a in mesh.axis_names if a in spans)
