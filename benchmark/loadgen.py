"""The load generator's process: ``python3 benchmark/loadgen.py``.

It never imports JAX (the parent holds the chip) and nothing of the program
but the generated gRPC stubs of the wire contract. The parent speaks to it
in JSON lines over stdin/stdout:

    {"op": "init", "generator": <name>, "traffic": {...}, "port": n, "context": {...}}
    {"op": "prepare", "seed": n}       build this seed's payloads
    {"op": "run", "seconds": s, "warm": bool}   drive the mix, answer with what was seen
    {"op": "quit"}

Each answer is one line. The generator named by the traffic file is found
as ``benchmark/generators/<name>.py`` and must define ``Generator``.
"""

from __future__ import annotations

import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    sys.path.insert(0, os.path.dirname(HERE))
    from benchmark import cells  # stdlib only: finds files by name, as the parent does

    out = sys.stdout
    sys.stdout = sys.stderr  # stray prints must not break the protocol
    gen = None
    for line in sys.stdin:
        msg = json.loads(line)
        try:
            if msg["op"] == "init":
                module = cells.load_module("generators", msg["generator"])
                gen = module.Generator(msg["traffic"], msg["port"], msg.get("context", {}))
                reply = {"ok": True}
            elif msg["op"] == "prepare":
                reply = {"ok": True, **(gen.prepare(int(msg["seed"])) or {})}
            elif msg["op"] == "run":
                reply = {"ok": True, **gen.run(float(msg["seconds"]), bool(msg.get("warm")))}
            elif msg["op"] == "quit":
                out.write(json.dumps({"ok": True}) + "\n")
                out.flush()
                return 0
            else:
                reply = {"ok": False, "error": f"unknown op {msg['op']!r}"}
        except Exception as e:  # noqa: BLE001 - the parent decides what a failure means
            reply = {"ok": False, "error": f"{type(e).__name__}: {e}", "traceback": traceback.format_exc()}
        reply["jax_imported"] = "jax" in sys.modules
        out.write(json.dumps(reply) + "\n")
        out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
