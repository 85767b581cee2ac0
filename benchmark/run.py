#!/usr/bin/env python3
"""One run of one cell: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

One new process, the only one that touches the chip. It boots the hub
in-process from the cell's configuration file, warms the driven family,
drives the cell's traffic from a child process that never imports JAX,
measures for ``--seconds``, frees the hub, checks a seeded sample of what
the window served against the plain reference, prints one JSON line and
exits. Without a TPU (or with fewer chips than the cell asks for) it exits
3 and prints no result. ``--rehearse`` runs tiny sizes on whatever backend
JAX has, to find wrong paths in a sandbox; its line says
``"rehearsal": true`` and is no measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import json
import os
import subprocess
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, hub, weights  # noqa: E402
from benchmark.generators.common import median, percentile  # noqa: E402
from benchmark.photos import photo_jpeg, pool_sizes  # noqa: E402

CACHE = os.path.join(HERE, ".cache")
TRACE_SECONDS = 3.0


class NoChip(Exception):
    pass


def log(*parts) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.1f}s]", *parts, file=sys.stderr, flush=True)


class Child:
    """The load generator's process and the JSON lines spoken with it."""

    def __init__(self, generator: str, traffic: dict, port: int, context: dict):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)  # it must never reach for the chip
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env, cwd=ROOT,
        )
        self.call(op="init", generator=generator, traffic=traffic, port=port, context=context)

    def send(self, **msg) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def receive(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"the load generator ended (exit code {self.proc.poll()})")
        reply = json.loads(line)
        if not reply.get("ok"):
            raise RuntimeError(f"load generator: {reply.get('error')}\n{reply.get('traceback', '')}")
        if reply.get("jax_imported"):
            raise RuntimeError("the load generator imported JAX")
        return reply

    def call(self, **msg) -> dict:
        self.send(**msg)
        return self.receive()

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.call(op="quit")
            except Exception:  # noqa: BLE001 - it is going away either way
                pass
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


def device_info(chips: int, rehearse: bool) -> dict:
    import jax

    devices = jax.devices()
    first = devices[0]
    info = {"platform": first.platform, "kind": first.device_kind, "count": len(devices)}
    if not rehearse and first.platform != "tpu":
        raise NoChip(f"JAX found no TPU: {info}")
    if not rehearse and len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chip(s), JAX reports {len(devices)}")
    return info


def memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()]
    return int(max(peaks))


def generator_context(cell: cells.Cell) -> dict:
    if cell.family != "vlm":
        return {}
    model = cell.config["models"]["vlm"]
    vocab = weights.vlm_vocab(model)
    special = {w: i for w, i in vocab.items() if not (w[:1] == "w" and w[1:].isdigit())}
    return {"vocab_size": model["config"]["text_config"]["vocab_size"], "special": special}


class Bench:
    """Set-up once, then any number of windows, then the checks."""

    def __init__(self, cell: cells.Cell, rehearse: bool):
        self.cell, self.rehearse = cell, rehearse
        self.handle = self.child = None
        self.context = generator_context(cell)

    def setup(self, seed: int) -> None:
        cell = self.cell
        hub.apply_env(cell.config)
        self.device = device_info(cell.chips, self.rehearse)
        self.peaks = None if self.rehearse else cells.peaks(self.device["kind"])
        os.makedirs(CACHE, exist_ok=True)
        t = time.perf_counter()
        self.names = {
            family: weights.ensure_model_dir(CACHE, cell.config["name"], family, model)
            for family, model in cell.config["models"].items()
        }
        log(f"model dirs ready in {time.perf_counter() - t:.1f}s: {self.names}")
        t = time.perf_counter()
        self.handle, self.settings = hub.boot(cell.config, CACHE, self.names, cell.family)
        log(f"hub booted in {time.perf_counter() - t:.1f}s on port {self.handle.port}")
        self.child = Child(cell.traffic["generator"], cell.traffic, self.handle.port, self.context)
        self.prepare(seed)
        t = time.perf_counter()
        warm = self.child.call(op="run", seconds=float(cell.traffic["warm_seconds"]), warm=True)
        log(f"warm-up traffic: {warm['attempted']} sent, {warm['failed']} failed, "
            f"{time.perf_counter() - t:.1f}s; errors={warm['errors'][:2]}")
        if warm["failed"]:
            raise RuntimeError(f"warm-up traffic failed: {warm['errors']}")

    def prepare(self, seed: int) -> dict:
        self.seed = seed
        self.prepared = self.child.call(op="prepare", seed=seed)
        return self.prepared

    def window(self, seconds: float, trace_dir: str | None = None) -> dict:
        """One measured window: the client's view, the program's counters
        before and after, and (traced) where the profiler wrote."""
        before = hub.counters()
        tracer = None
        if trace_dir:
            tracer = threading.Thread(target=_trace_slice, args=(trace_dir, seconds), daemon=True)
            tracer.start()
        t = time.perf_counter()
        client = self.child.call(op="run", seconds=seconds, warm=False)
        took = time.perf_counter() - t
        after = hub.counters()
        if tracer:
            tracer.join()
        return {"client": client, "before": before, "after": after, "seconds": seconds, "took": took,
                "memory_peak_bytes": memory_peak(), "seed": self.seed, "prepared": self.prepared,
                "settings": self.settings}

    def teardown(self) -> None:
        """Stop the child and the hub and free the device for the reference."""
        if self.child is not None:
            self.child.close()
            self.child = None
        if self.handle is not None:
            self.handle.drain_and_stop(drain_s=2.0)
            for svc in list(getattr(self.handle, "services", {}).values()):
                try:
                    svc.close()
                except Exception:  # noqa: BLE001 - already closed by the drain
                    pass
            self.handle = None
            hub.shutdown_pools()
        import jax

        gc.collect()
        jax.clear_caches()
        gc.collect()

    # -- the sample and its check ------------------------------------------

    def sample(self, result: dict) -> dict:
        """What the reference is asked to judge, drawn from the seed out of
        what the window finished (the longest request always in it)."""
        import numpy as np

        cell, seed, client = self.cell, result["seed"], result["client"]
        if cell.family == "clip":
            sizes = pool_sizes(cell.traffic["photo_pool"])
            picked = sorted(int(k) for k in client["sample"])
            return {
                "jpegs": [photo_jpeg(seed, i, sizes[i], cell.traffic["jpeg_quality"], cell.traffic["noise"])
                          for i in picked],
                "served": [client["sample"][str(i)] for i in picked],
            }
        finished = [r for r in client["finished"] if r["tokens"]]
        if not finished:
            return {"requests": []}
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 99])
        longest = max(range(len(finished)), key=lambda i: len(finished[i]["tokens"]))
        rest = [i for i in rng.permutation(len(finished)) if i != longest]
        n = int(cell.traffic["check"]["sample_requests"])
        special = self.context["special"]
        reference = cell.reference()
        side = int(cell.traffic["image_long_side"])
        return {"requests": [
            {"jpeg": photo_jpeg(seed, r["image"], side, cell.traffic["jpeg_quality"], cell.traffic["noise"]),
             "prompt_ids": reference.prompt_ids(result["prepared"]["instruction_ids"], special),
             "tokens": r["tokens"]}
            for r in (finished[i] for i in [longest, *rest[: n - 1]])
        ]}

    def compare(self, sample: dict, control: bool = False) -> dict:
        cell = self.cell
        family = cell.family
        model_dir = os.path.join(CACHE, "models", self.names[family])
        if not (sample.get("requests") or sample.get("jpegs")):
            return {"nothing_to_compare": 1.0}
        return cell.reference().compare(
            sample, cell.config["models"][family], model_dir, cell.config["precision"][family], control
        )


def _trace_slice(trace_dir: str, seconds: float) -> None:
    """Trace a slice in the middle of the window: a whole window's trace is
    too large to read back inside a run's time limit."""
    import jax

    length = min(TRACE_SECONDS, seconds / 3)
    time.sleep(max(0.0, (seconds - length) / 2))
    jax.profiler.start_trace(trace_dir)
    time.sleep(length)
    jax.profiler.stop_trace()


def _debug_dump(path: str, cell: cells.Cell, result: dict, trace: dict, line: dict) -> None:
    os.makedirs(path, exist_ok=True)
    summary = []
    for plane in trace["planes"]:
        for ln in plane["lines"]:
            totals: dict[str, list] = {}
            for name, _, dur in ln["events"]:
                t = totals.setdefault(name, [0, 0])
                t[0] += dur
                t[1] += 1
            top = sorted(totals.items(), key=lambda kv: -kv[1][0])[:25]
            summary.append({"plane": plane["name"], "line": ln["name"], "events": len(ln["events"]),
                            "top": [[n[:160], d / 1e9, c] for n, (d, c) in top]})
    client = {k: v for k, v in result["client"].items() if k not in ("sample", "finished")}
    with open(os.path.join(path, f"{cell.name}.debug.json"), "w") as f:
        json.dump({"trace": summary, "before": result["before"], "after": result["after"],
                   "client": client, "line": line}, f)
    devs = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    if devs:  # a short excerpt in neutral form, for the reduction's test
        t0 = min(ev[1] for ln in devs[0]["lines"] for ev in ln["events"])
        cut = {"planes": [{"name": p["name"], "lines": [
            {"name": ln["name"], "events": [ev for ev in ln["events"] if t0 <= ev[1] < t0 + 30_000_000][:400]}
            for ln in p["lines"]]} for p in trace["planes"]]}
        with open(os.path.join(path, f"{cell.name}.excerpt.json"), "w") as f:
            json.dump(cut, f)


def limits(cell: cells.Cell) -> dict:
    """The limit of each number compared, as the configuration's file states
    it for the driven family (``"limits": {"<family>": {"<number>": x}}``)."""
    return cell.config["limits"][cell.family]


def judge(numbers: dict, limit: dict, client: dict) -> tuple[bool, dict]:
    compared = {}
    for name, value in numbers.items():
        if name.startswith("_"):
            continue
        compared[name] = {"value": value, "limit": limit[name]}
    compared["requests_failed"] = {"value": client["failed"], "limit": 0}
    ok = all(v["value"] <= v["limit"] for v in compared.values()) and client["attempted"] > 0
    return ok, compared


def end_to_end(cell: cells.Cell, result: dict, setup_s: float) -> dict:
    client, names = result["client"], cell.traffic["end_to_end"]
    q = float(names.get("tail_q", 95))  # which percentile of all requests the mix's latency metric is
    if cell.family == "clip":
        rate = client["completed_in_window"] / client["window_s"]
        tail = percentile(client["latency_ms"], q)
    else:
        rate = client["tokens_in_window"] / client["window_s"]
        tail = percentile(client["ttft_ms"], q)
    values = {names["rate"]: rate, names["tail"]: tail, "setup_s": setup_s}
    units = {m["name"]: m["unit"] for m in cell.bench["end_to_end"]}
    wanted = [m["name"] for m in cell.end_to_end() if m["name"] in values]
    if len(wanted) < 2:  # a rehearsal's cell is in no metric's list
        wanted = list(values)
    return {n: {"value": values[n], "unit": units.get(n, "")} for n in wanted if values.get(n) is not None}


def per_layer(cell: cells.Cell, ctx: dict) -> dict:
    out = {}
    for metric in cell.per_layer():
        spec, reader = cell.layer_metric(metric["name"])
        value = reader.read(ctx, spec)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--debug-dir", help="write what the run saw (counters, trace summary) there")
    args = ap.parse_args(argv)

    cell = cells.Cell(args.workload, rehearse=args.rehearse)
    bench = Bench(cell, args.rehearse)
    trace_dir = os.path.join(CACHE, "trace", f"{cell.name}.{os.getpid()}") if args.trace else None
    try:
        try:
            bench.setup(args.seed)
        except NoChip as e:
            log(f"no run: {e}")
            return 3
        setup_s = time.perf_counter() - T_START
        log(f"set-up {setup_s:.1f}s; window of {args.seconds}s opens")
        result = bench.window(args.seconds, trace_dir)
        log(f"window closed after {result['took']:.1f}s; drain {result['client']['drain_s']:.2f}s; "
            f"errors={result['client']['errors'][:3]}")
        sample = bench.sample(result)
        device = {**bench.device, "memory_peak_bytes": result["memory_peak_bytes"]}
        line = {"correct": False, "attempted": result["client"]["attempted"],
                "failed": result["client"]["failed"]}
        if args.trace:
            from benchmark import trace_reduce

            t = time.perf_counter()
            trace = trace_reduce.from_xplane(trace_reduce.find_xplane(trace_dir))
            reduced = trace_reduce.reduce(trace)
            log(f"trace read in {time.perf_counter() - t:.1f}s: busy {reduced['busy_s']:.3f}s of "
                f"{reduced['window_s']:.3f}s")
            ctx = {"cell": cell, "result": result, "trace": trace, "reduced": reduced,
                   "peaks": bench.peaks, "median": median, "percentile": percentile}
            line["metrics"] = per_layer(cell, ctx)
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            line["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
            if args.debug_dir:
                _debug_dump(args.debug_dir, cell, result, trace, line)
            del trace, ctx
        else:
            line["metrics"] = end_to_end(cell, result, setup_s)
        line["device"] = device
    finally:
        bench.teardown()
        if trace_dir:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
    t = time.perf_counter()
    numbers = bench.compare(sample)
    line["correct"], compared = judge(numbers, limits(cell), result["client"])
    log(f"reference ran in {time.perf_counter() - t:.1f}s: {numbers}")
    if args.rehearse:
        line["rehearsal"] = True
    line["compared"] = compared
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except Exception:  # noqa: BLE001 - no result line, a code other than 0
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # daemon threads of the stopped server must not hold the exit
