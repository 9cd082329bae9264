"""Cells by name: a configuration file, a traffic mix, a chip count and
the limits that decide ``correct``.

A cell listed in ``BENCHMARK.json`` takes its configuration, mix and
chips from there.  Any other name ``<config>.<traffic>`` is a cell as
soon as ``configs/<config>.json`` and ``traffic/<traffic>.json`` exist.
A cell's correctness limits are ``checks/<cell>.json`` (``bench/check.py``
says what they compare); a cell without that file is never correct.

So a new cell needs new files and no edit: ``configs/<config>.json``
where the configuration is new, ``traffic/<traffic>.json`` where the mix
is new, ``checks/<cell>.json``, and the cell's entry in ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Optional

BENCH = pathlib.Path(__file__).resolve().parent
CONFIG_DIR = BENCH / "configs"
TRAFFIC_DIR = BENCH / "traffic"
CHECK_DIR = BENCH / "checks"
METRIC_DIR = BENCH / "metrics"
BENCHMARK_JSON = BENCH.parent / "BENCHMARK.json"
# what bench/run.py can measure end to end; a cell made of files alone
# reports all of them
END_TO_END = ("tokens_per_s", "ttft_p90_s", "tpot_p90_ms", "setup_s")


@dataclasses.dataclass
class Cell:
    name: str
    config: dict  # configs/<config>.json
    mix: dict  # traffic/<traffic>.json
    traffic: str
    chips: int
    per_layer: list  # names of the per-layer metrics this cell reports
    end_to_end: list  # names of the end-to-end metrics it reports
    limits: dict  # checks/<cell>.json as written; {} without the file


def _benchmark() -> Optional[dict]:
    if BENCHMARK_JSON.is_file():
        return json.loads(BENCHMARK_JSON.read_text())
    return None


def _split(name: str, config_dir: pathlib.Path, traffic_dir: pathlib.Path):
    for i, ch in enumerate(name):
        if ch == ".":
            conf, mix = name[:i], name[i + 1:]
            if (config_dir / f"{conf}.json").is_file() and (
                traffic_dir / f"{mix}.json"
            ).is_file():
                return conf, mix
    return None


def resolve(
    name: str,
    config_dir: pathlib.Path = CONFIG_DIR,
    traffic_dir: pathlib.Path = TRAFFIC_DIR,
    metric_dir: pathlib.Path = METRIC_DIR,
    check_dir: pathlib.Path = CHECK_DIR,
) -> Cell:
    bench = _benchmark()
    conf = mix = None
    chips = 1
    per_layer = end_to_end = None
    if bench is not None:
        for w in bench["workloads"]:
            if w["name"] == name:
                conf, mix, chips = w["config"], w["traffic"], w["chips"]
                per_layer, end_to_end = (
                    [m["name"] for m in bench[kind]
                     if name in m.get("workloads", [name])]
                    for kind in ("per_layer", "end_to_end"))
    if conf is None:
        found = _split(name, config_dir, traffic_dir)
        if found is None:
            raise KeyError(
                f"no cell {name!r}: not in BENCHMARK.json, and no "
                f"<config>.<traffic> split names files in {config_dir} "
                f"and {traffic_dir}"
            )
        conf, mix = found
    if per_layer is None:  # a cell made of files alone reports every reader
        per_layer = sorted(p.stem for p in metric_dir.glob("*.py")
                           if not p.stem.startswith("_"))
    if end_to_end is None:
        end_to_end = list(END_TO_END)
    config = json.loads((config_dir / f"{conf}.json").read_text())
    mix_d = json.loads((traffic_dir / f"{mix}.json").read_text())
    limits_file = check_dir / f"{name}.json"
    limits = json.loads(limits_file.read_text()) if limits_file.is_file() else {}
    return Cell(name, config, mix_d, mix, chips, per_layer, end_to_end, limits)
