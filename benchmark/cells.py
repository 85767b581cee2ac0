"""How the harness finds what belongs to a cell: by name, in files.

``BENCHMARK.json`` (repo root) names cells, configurations and metrics. All
else is found from those names, so a later PR adds files and entries and
edits nothing that is there:

- a cell ``<name>``         -> its ``workloads`` entry: ``config`` + ``traffic``
- a configuration           -> the ``file`` of its ``configs`` entry
- a model entry of it       -> ``models.<family>`` of that file; its optional
  (``clip``, ``vlm``)          ``tensors`` key -> ``benchmark/tensors/<t>.py``,
                               which lists the checkpoint (``weights.listing``);
                               its optional ``counts`` key ->
                               ``benchmark/counts/<c>.py``, the work behind the
                               whole-step shares (``readers/common.counts``).
                               An entry without a key means the family's own
                               name: ``tensors/vlm.py``, ``counts/clip.py``
- a traffic mix ``<t>``     -> ``benchmark/traffic/<t>.json``; its ``generator``
                               key -> ``benchmark/generators/<g>.py``; its
                               ``reference`` key -> ``benchmark/references/<r>.py``
- a per-layer metric ``<m>``-> ``benchmark/layer_metrics/<m>.json``; its
                               ``reader`` key -> ``benchmark/readers/<r>.py``;
                               an optional ``roofline`` key ->
                               ``benchmark/rooflines/<k>.py``

What a PR that adds a configuration of a new decoder architecture touches,
and all it touches (``tests/test_cells.py::add_decoder`` does exactly this):

- new files: ``tensors/<t>.py`` (``tensors(cfg)``, optionally
  ``special_words(cfg)``), ``counts/<c>.py`` (``image_flops``,
  ``prefill_flops``, ``decode_token_flops``, ``decode_step_bytes``),
  ``references/<r>.py`` (``compare``, ``fault``, ``prompt_ids``),
  ``configs/<name>.json`` whose ``models.vlm`` names the first two, and
  ``traffic/<mix>.json`` that names the reference (a mix belongs to one
  reference, so a new decoder brings a mix of its own, data only);
- ``BENCHMARK.json``: one ``configs`` entry, one ``workloads`` entry for each
  cell, and the cell's name appended to the ``workloads`` list of every
  metric it reports: ``caption_tokens_per_s``, ``ttft_p50_ms``,
  ``vlm_step_mfu``, ``vlm_step_hbm_pct`` and the captioning cell's other
  per-layer metrics;
- a kernel of its own brings ``layer_metrics/<m>.json``, ``rooflines/<k>.py``
  and, where ``kernel_roofline``'s grouped-query call does not fit it, a
  reader.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class CellError(Exception):
    """The cell, or a file it names, cannot be found or read."""


def _read_json(path: str) -> dict:
    if not os.path.exists(path):
        raise CellError(f"{os.path.relpath(path, ROOT)} does not exist")
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def load_module(kind: str, name: str, here: str = HERE):
    """``benchmark/<kind>/<name>.py`` as a module, loaded by path."""
    path = os.path.join(here, kind, f"{name}.py")
    if not os.path.exists(path):
        raise CellError(f"benchmark/{kind}/{name}.py does not exist")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One ``workloads`` entry with everything it names, read from ``root``."""

    def __init__(self, name: str, root: str = ROOT, rehearse: bool = False):
        self.root = root
        self.here = os.path.join(root, "benchmark")
        self.bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if name in entries:
            self.entry = entries[name]
        elif rehearse and "." in name:
            # a rehearsal may pair any configuration file with any mix
            config, traffic = name.rsplit(".", 1)
            self.entry = {"name": name, "config": config, "traffic": traffic, "chips": 1}
        else:
            raise CellError(f"no workload {name!r} in BENCHMARK.json (has: {sorted(entries)})")
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        config_name = self.entry["config"]
        rel = configs[config_name]["file"] if config_name in configs else f"benchmark/configs/{config_name}.json"
        self.config = _read_json(os.path.join(root, rel))
        self.traffic = _read_json(os.path.join(self.here, "traffic", f"{self.entry['traffic']}.json"))
        if rehearse:
            self.traffic.update(self.traffic.get("rehearse", {}))
        self.family = self.traffic["family"]

    def _applies(self, metric: dict) -> bool:
        listed = metric.get("workloads")
        if listed is None or self.name in listed:
            return True
        # a rehearsal's cell is in no list: it takes the metrics of its mix
        known = any(w["name"] == self.name for w in self.bench["workloads"])
        return not known and any(w.endswith("." + self.entry["traffic"]) for w in listed)

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"] if self._applies(m)]

    def per_layer(self) -> list[dict]:
        return [m for m in self.bench["per_layer"] if self._applies(m)]

    def layer_metric(self, name: str) -> tuple[dict, object]:
        """The metric's own file and the reader module it names."""
        spec = _read_json(os.path.join(self.here, "layer_metrics", f"{name}.json"))
        return spec, load_module("readers", spec["reader"], self.here)

    def reference(self):
        return load_module("references", self.traffic["reference"], self.here)


def peaks(device_kind: str, here: str = HERE) -> dict:
    table = _read_json(os.path.join(here, "peaks.json"))
    if device_kind not in table["devices"]:
        raise CellError(f"device kind {device_kind!r} is not in benchmark/peaks.json: add it with its source")
    return table["devices"][device_kind]
