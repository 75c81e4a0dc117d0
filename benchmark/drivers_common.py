"""Set-up shared by the drivers: the ruleset through the program's parse."""

from __future__ import annotations

import os

import harness
from gen import rules


def prepare_ruleset(cell: harness.Cell):
    """Draw the ruleset, have the program parse and pack it (its normal path)."""
    from ruleset_analysis_tpu import cli
    from ruleset_analysis_tpu.hostside import pack

    rs = rules.make_ruleset(cell.config)
    rows = rules.expand(rs)
    cfg_path = os.path.join(cell.work, f"{rs.firewall}.cfg")
    with open(cfg_path, "w", encoding="utf-8") as f:
        f.write(rs.text)
    prefix = os.path.join(cell.work, "packed")
    if cli.main(["parse-acls", cfg_path, "--out", prefix]) != 0:
        raise harness.BenchError("parse-acls failed on the generated configuration")
    return rs, rows, pack.load_packed(prefix)


def analysis_config(cell: harness.Cell, **over):
    from ruleset_analysis_tpu.config import AnalysisConfig, SketchConfig

    a = cell.config["analysis"]
    kw = dict(batch_size=a["batch_size"], exact_counts=a["exact_counts"],
              sketch=SketchConfig(**a["sketch"]))
    kw.update(over)
    return AnalysisConfig(**kw)
