"""Device time of one program's operations by the ``lumen.`` scope the
program's code put them under (``benchmark/trace_scopes.py``).

``spec``: ``module_pattern`` (matched on an operation's program: the step
program is ``step_block``); ``scope_pattern`` (matched on its scope path) or
``unscoped`` (true: the operations that carry no ``lumen.`` name, which no
line of the program asked for, such as a copy the compiler inserted);
``per``: ``step`` reads milliseconds a decode step (the summed time over the
program's runs in the trace, its ``XLA Modules`` events, times
``decode_block``, as ``vlm_step_hbm`` counts steps), ``share`` a percentage
of the program's summed operation time.

Operations are summed as ``trace_reduce.time_by_name`` sums them: loops and
branches are left out (what runs inside them is on the same line), and an
asynchronous copy or slice counts its ``-start`` and its ``-done`` each at
its own duration, so the sum can pass the time the program held the device.
Returns None where no operation of the program carries a ``lumen.`` scope
(a program built without scopes; the compile cache ignores op metadata, so
another program of the same process may carry the names of a tree that
compiled it first) or the program did not run."""

import re

from benchmark import trace_reduce, trace_scopes


def read(ctx, spec):
    rx = re.compile(spec["module_pattern"])
    mine = [op for op in trace_scopes.first_plane(trace_scopes.of(ctx))
            if rx.search(op[3]) and trace_reduce.stem(op[0]) not in trace_reduce._CONTAINERS]
    if not any(op[4] for op in mine):
        return None
    if spec.get("unscoped"):
        part = sum(op[2] for op in mine if not op[4])
    else:
        scope = re.compile(spec["scope_pattern"])
        part = sum(op[2] for op in mine if scope.search(op[4]))
    if spec["per"] == "share":
        return 100.0 * part / sum(op[2] for op in mine)
    planes = trace_reduce.device_planes(ctx["trace"])
    runs = sum(1 for line in planes[0]["lines"] if line["name"] == "XLA Modules"
               for ev in line["events"] if rx.search(ev[0])) if planes else 0
    if not runs:
        return None
    block = int(ctx["result"]["settings"]["vlm"]["decode_block"])
    return part / 1e6 / (runs * block)
