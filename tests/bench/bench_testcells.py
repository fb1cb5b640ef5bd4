"""Cells for the benchmark's tests: a cell and everything it names,
written as new files under a temporary root, at a size the CPU runs in a
second."""
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

TINY_CONFIG = {"d": 24, "samples": 480, "n_nodes": 6, "r": 3,
               "graph": {"kind": "erdos_renyi", "p": 0.5, "seed": 3},
               "alpha": 2.0}
TINY_TRAFFIC = {"entry": "sdot", "operand": "cov",
                "schedule": {"slope": 0, "offset": 5}, "t_outer": 30}
# Set as the chip cells' limits are, from readings on the CPU at this size
# over seeds 1-12, 40 solves each, of the cov, data and four-device
# sdot_spmd cells: the program's largest gap 3.01e-7, the control's least
# 1.6e-6 (data). A steeper spectrum (alpha 2) and 30 outer
# iterations let each solve converge; at alpha 1.2 or 12 iterations a few
# initial iterates leave the iterate unsettled, and its gap reads up to
# 5e-4 in the program alone.
TINY_LIMIT = 1e-6


def write_cell(root: pathlib.Path, name="tiny.mix", config=None,
               traffic=None, limit=TINY_LIMIT, chips=1,
               metrics=("device.idle_share",)):
    """A cell and everything it names, as files under ``root``."""
    cfg_name, mix = name.split(".", 1)
    config, traffic = config or TINY_CONFIG, traffic or TINY_TRAFFIC
    bench = root / "bench"
    for sub in ("configs", "traffic", "checks", "metrics", "graphs",
                "entries"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    (bench / "configs" / f"{cfg_name}.json").write_text(json.dumps(config))
    (bench / "traffic" / f"{mix}.json").write_text(json.dumps(traffic))
    (bench / "checks" / f"{name}.json").write_text(
        json.dumps({"subspace_gap_max": limit}))
    for sub, stem in [("metrics", m) for m in metrics] + [
            ("graphs", config["graph"]["kind"]),
            ("entries", traffic["entry"])]:
        src = ROOT / "bench" / sub / f"{stem}.py"
        if src.exists():
            shutil.copy(src, bench / sub / f"{stem}.py")
    doc = {
        "configs": [{"name": cfg_name,
                     "file": f"bench/configs/{cfg_name}.json"}],
        "workloads": [{"name": name, "config": cfg_name, "traffic": mix,
                       "chips": chips}],
        "end_to_end": [{"name": n, "unit": u}
                       for n, u in (("solve_ms", "ms"),
                                    ("solve_p90_ms", "ms"),
                                    ("peak_hbm_mb", "MB"),
                                    ("setup_s", "s"))],
        "per_layer": [{"name": m, "unit": "%", "moves": "solve_ms",
                       "workloads": [name]} for m in metrics],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root
