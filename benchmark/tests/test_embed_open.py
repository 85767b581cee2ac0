"""The open-loop generator of ``embed_interactive`` (PR 30): its schedule is
a function of the seed, and it never imports JAX."""

import json
import os
import subprocess
import sys

from benchmark import cells

ROOT = cells.ROOT


def test_the_open_loop_schedule_is_a_function_of_the_seed():
    embed_open = cells.load_module("generators", "embed_open")
    traffic = cells._read_json(os.path.join(cells.HERE, "traffic", "embed_interactive.json"))
    big = 2**31 + 99
    a = embed_open.schedule(big, 0, 40.0, traffic, 48)
    assert a == embed_open.schedule(big, 0, 40.0, traffic, 48)
    assert a != embed_open.schedule(big + 1, 0, 40.0, traffic, 48) and a != embed_open.schedule(big, 500, 40.0, traffic, 48)
    due = [x["due"] for x in a]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 40.0
    rate = float(traffic["rate_rps"])
    assert abs(len(a) - rate * 40) < 5 * (rate * 40) ** 0.5  # Poisson: within five standard deviations
    images = [x for x in a if x["kind"] == "image"]
    assert abs(len(images) / len(a) - 0.7) < 0.06 and all(0 <= x["photo"] < 48 for x in images)
    words = [len(x["text"].split()) for x in a if x["kind"] == "text"]
    assert min(words) >= 3 and max(words) <= 12
    assert all(w[:1] == "w" and 0 < int(w[1:]) < 49408 for x in a if x["kind"] == "text" for w in x["text"].split())


def test_the_open_loop_generator_never_imports_jax():
    traffic = cells._read_json(os.path.join(cells.HERE, "traffic", "embed_interactive.json"))
    traffic.update(traffic["rehearse"])
    msgs = [{"op": "init", "generator": "embed_open", "traffic": traffic, "port": 1, "context": {}},
            {"op": "prepare", "seed": 2**31 + 3}, {"op": "quit"}]
    out = subprocess.run([sys.executable, os.path.join(cells.HERE, "loadgen.py")], cwd=ROOT, text=True,
                         input="".join(json.dumps(m) + "\n" for m in msgs), capture_output=True, timeout=120)
    replies = [json.loads(l) for l in out.stdout.splitlines()]
    assert [r["ok"] for r in replies] == [True, True, True], out.stderr
    assert replies[1]["photos"] == 8 and not any(r.get("jax_imported") for r in replies)
