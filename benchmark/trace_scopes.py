"""Device operations named by the code that asked for them.

The step and chunk programs put ``jax.named_scope`` names around their parts
(``lumen.state``, ``lumen.decode``, ``lumen.kv_write``, ...:
``lumen_tpu/utils/trace.py``, ``docs/OBSERVABILITY.md``). A scope is op
metadata: each HLO instruction carries the path it was traced under in its
``op_name``. A profile keeps every program it saw run as an HLO proto on its
``/host:metadata`` plane (the ``Hlo Proto`` stat of an event metadata named
``<program>(<id>)``). An operation on a device's ``XLA Ops`` line is an
instruction of the program whose ``XLA Modules`` event holds it in time.
:func:`scoped_form` joins the two.

The result stays beside ``trace_reduce``'s neutral form, which keeps three
elements an event and is not touched: ``{"<device plane>": [[name, start_ns,
duration_ns, program, scope, shape], ...]}``, the operations of its op lines
in order of start. ``program`` is the program's name without its id
(``jit__step_block_impl``); ``scope`` the ``lumen.`` part of the op_name with
JAX's own wrappers, the operation and the Flax layer indices left out
(``lumen.decode/VLMModel.decode_paged/decoder/layers/attn/lumen.kv_write``),
or ``""`` where no ``lumen.`` name is on it (an operation no line of the
program asked for: a copy the compiler inserted, a program without scopes);
``shape`` the instruction's output shape.

``python3 benchmark/trace_scopes.py <trace dir or .xplane.pb>`` prints the
operations' seconds by scope (:func:`device_scopes`): a ``jax.profiler``
trace, or one the sidecar's ``POST /profiler/start`` wrote.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys
import time

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce  # noqa: E402

#: an HLO instruction line: ``%name = shape opcode(...)``
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: components of an op_name that JAX's transformations add
_WRAPPER = re.compile(r"^(while|body|cond|closed_call|checkpoint|remat|scan|pjit|branch_\d+_fun)$|\(")


# -- the profile's HLO protos --------------------------------------------------


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf, lo: int = 0, hi: int | None = None):
    """``(field, value)`` of one protobuf message in ``buf[lo:hi]``: an int
    for a varint, ``(start, end)`` for a length-delimited field."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _metadata_plane(path: str) -> bytes | None:
    """The serialized ``/host:metadata`` plane of an XSpace file, read
    without loading the device and host planes (``XSpace.planes`` = 1,
    ``XPlane.name`` = 2, written after the plane's ``id``)."""
    with open(path, "rb") as f:
        size = os.fstat(f.fileno()).st_size
        pos = 0
        while pos < size:
            f.seek(pos)
            head = f.read(20)
            key, i = _varint(head, 0)
            n, i = _varint(head, i)
            start, pos = pos + i, pos + i + n
            if key != (1 << 3 | 2):
                continue
            f.seek(start)
            probe = f.read(min(n, 256))
            for field, value in _fields(probe, 0, len(probe)):
                if field == 2:
                    if probe[value[0]:value[1]] == b"/host:metadata":
                        f.seek(start)
                        return f.read(n)
                    break
    return None


def hlo_protos(path: str) -> dict[str, bytes]:
    """``{"<program>(<id>)": HloModuleProto bytes}`` of a trace file."""
    plane = _metadata_plane(path)
    if plane is None:
        return {}
    stat_names, metas = {}, []
    for field, value in _fields(plane):
        if field not in (4, 5):  # event_metadata, stat_metadata: map entries
            continue
        for key, entry in _fields(plane, *value):
            if key != 2:
                continue
            if field == 4:
                metas.append(entry)
                continue
            sid = name = None  # XStatMetadata: id = 1, name = 2
            for k, v in _fields(plane, *entry):
                if k == 1:
                    sid = v
                elif k == 2:
                    name = bytes(plane[v[0]:v[1]]).decode()
            stat_names[sid] = name
    out = {}
    for lo, hi in metas:
        name, blobs = None, []
        for k, v in _fields(plane, lo, hi):
            if k == 2:
                name = bytes(plane[v[0]:v[1]]).decode()
            elif k == 5:  # XStat: metadata_id = 1, bytes_value = 6
                sid, blob = None, None
                for sk, sv in _fields(plane, *v):
                    if sk == 1:
                        sid = sv
                    elif sk == 6:
                        blob = sv
                if blob is not None:
                    blobs.append((sid, blob))
        for sid, (b0, b1) in blobs:
            if stat_names.get(sid) == "Hlo Proto" and name:
                for k, v in _fields(plane, b0, b1):  # HloProto.hlo_module = 1
                    if k == 1:
                        out[name] = bytes(plane[v[0]:v[1]])
    return out


def instructions(module_proto: bytes) -> dict[str, tuple[str, str]]:
    """``{instruction: (op_name, output shape)}`` of one program, fused
    computations' bodies included; an instruction without metadata has
    ``""``. ``op_name`` is the first of the names XLA joins with ``;``."""
    from jax._src.lib import xla_client

    text = xla_client._xla.HloModule.from_serialized_hlo_module_proto(module_proto).to_string()
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            n = _OP_NAME.search(line)
            out[m.group(1)] = (n.group(1).split(";")[0] if n else "", m.group(2))
    return out


def scope_path(op_name: str) -> str:
    """The ``lumen.`` part of an op_name: from its first ``lumen.`` name on,
    without JAX's wrappers, the operation itself and the indices Flax gives
    a list's modules; ``""`` where it holds no ``lumen.`` name."""
    parts = op_name.split("/")[:-1]
    first = next((i for i, p in enumerate(parts) if p.startswith("lumen.")), None)
    if first is None:
        return ""
    return "/".join(re.sub(r"_\d+$", "", p) for p in parts[first:] if not _WRAPPER.search(p))


# -- operations joined to their scopes -----------------------------------------


def scoped_form(trace: dict, protos: dict[str, bytes]) -> dict:
    """The scoped form (module docstring) of a neutral-form ``trace``'s
    device planes, each operation joined to the instruction of the program
    whose ``XLA Modules`` event holds its start. Where the profile names a
    run's program by another id than its protos do, the first program of
    the same name that has the instruction stands in."""
    tables: dict[str, dict] = {}
    by_name: dict[str, list[str]] = {}
    for key in sorted(protos):
        by_name.setdefault(key.split("(")[0], []).append(key)

    def table(key: str) -> dict:
        if key not in tables:
            tables[key] = instructions(protos[key])
        return tables[key]

    def lookup(program: str, op: str) -> tuple[str, str]:
        keys = [program] if program in protos else by_name.get(program.split("(")[0], [])
        return next((table(k)[op] for k in keys if op in table(k)), ("", ""))

    out = {}
    for plane in trace_reduce.device_planes(trace):
        runs = sorted((ev[1], ev[1] + ev[2], ev[0]) for line in plane["lines"] if line["name"] == "XLA Modules"
                      for ev in line["events"])
        starts = [r[0] for r in runs]
        ops = []
        for name, start, dur in trace_reduce.op_events(plane):
            k = bisect.bisect_right(starts, start) - 1
            program = runs[k][2] if k >= 0 and start < runs[k][1] else ""
            op_name, shape = lookup(program, name.split(" = ", 1)[0].strip().lstrip("%"))
            ops.append([name, start, dur, program.split("(")[0], scope_path(op_name), shape])
        out[plane["name"]] = ops
    return out


def first_plane(scoped: dict) -> list[list]:
    """The operations of the first device plane, the one ``trace_reduce``
    reads (``[]`` where there is none)."""
    return next(iter(scoped.values()), [])


def device_scopes(scoped: dict, top: int = 15) -> list[list]:
    """The ``top`` largest ``[scope path/operation, seconds]`` of the first
    device plane over all programs; an operation without a scope is named
    by its program, its operation and its output shape. Loops and branches
    are left out, and an asynchronous pair counts its ``-start`` and its
    ``-done`` each at its own duration, as ``trace_reduce.time_by_name``
    counts them."""
    totals: dict[str, int] = {}
    for name, _, dur, program, scope, shape in first_plane(scoped):
        stem = trace_reduce.stem(name)
        if stem in trace_reduce._CONTAINERS:
            continue
        key = f"{scope}/{stem}" if scope else f"({program}) {stem} {shape}".rstrip()
        totals[key] = totals.get(key, 0) + dur
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[k[:200], d / 1e9] for k, d in ranked]


def run_trace_dir(cell) -> str:
    """Where ``benchmark/run.py`` has this process trace ``cell``'s window."""
    return os.path.join(cell.here, ".cache", "trace", f"{cell.name}.{os.getpid()}")


def of(ctx: dict) -> dict:
    """The scoped form of a reader's trace: ``ctx["trace"]["scopes"]`` where
    the neutral form carries it (a recorded trace), else joined once from
    the run's neutral form and the HLO protos of its trace file, and kept in
    ``ctx`` for the next reader; ``{}`` where there is no file. The first
    join logs :func:`device_scopes`."""
    if "scopes" not in ctx:
        scoped = ctx["trace"].get("scopes")
        if scoped is None:
            scoped = {}
            cell = ctx.get("cell")
            try:
                path = trace_reduce.find_xplane(run_trace_dir(cell)) if cell is not None else None
            except FileNotFoundError:
                path = None
            if path:
                t = time.perf_counter()
                scoped = scoped_form(ctx["trace"], hlo_protos(path))
                print(f"device scopes, joined in {time.perf_counter() - t:.1f}s: {json.dumps(device_scopes(scoped))}",
                      file=sys.stderr, flush=True)
        ctx["scopes"] = scoped
    return ctx["scopes"]


def main(argv: list[str]) -> int:
    path = argv[0] if argv[0].endswith(".xplane.pb") else trace_reduce.find_xplane(argv[0])
    scoped = scoped_form(trace_reduce.from_xplane(path), hlo_protos(path))
    for key, seconds in device_scopes(scoped, top=int(argv[1]) if len(argv) > 1 else 15):
        print(f"{seconds:12.6f}  {key}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
