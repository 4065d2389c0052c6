"""Multi-pod dry-run for one (arch x shape) cell + its roofline terms.

    PYTHONPATH=src python examples/multipod_dryrun.py --arch qwen3-14b \
        --shape decode_32k

Runs in a subprocess so the 512 placeholder devices never leak into the
calling process.  For the full 40-cell sweep use
``python -m repro.launch.dryrun --all``.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--mesh", default="both")
    args = ap.parse_args()

    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
                   JAX_PLATFORMS="cpu")  # placeholder devices only
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", args.arch, "--shape", args.shape,
               "--mesh", args.mesh, "--out", td, "--tag", "x"]
        subprocess.run(cmd, env=env, check=True)

        from repro.launch.roofline import fmt_s, roofline_row

        for f in sorted(Path(td, "x").glob("*.json")):
            cell = json.loads(f.read_text())
            r = roofline_row(cell)
            if not r:
                print(f.name, cell.get("status"), cell.get("reason", ""))
                continue
            print(f"\n{r['arch']} / {r['shape']} / {r['mesh']}  "
                  f"({cell['devices']} chips)")
            print(f"  compute  term: {fmt_s(r['compute_s'])}")
            print(f"  memory   term: {fmt_s(r['memory_s'])}")
            print(f"  collective  : {fmt_s(r['collective_s'])}")
            print(f"  bottleneck  : {r['dominant']}  "
                  f"(roofline fraction {r['roofline_fraction']:.1%}, "
                  f"useful-FLOP ratio {r['useful_ratio']:.1%})")


if __name__ == "__main__":
    main()
