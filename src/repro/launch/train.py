"""Training launcher with auto-restart supervision.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2_5_3b \
      --steps 200 --seq 256 --batch 8 [--supervise]

--supervise wraps the run in the in-process supervisor: preemption
(SIGTERM) or injected node failures checkpoint-and-restart until the step
budget completes.  On a real cluster the same entry point runs under the
cluster's restart policy (exit code 42 = retry).
"""
from __future__ import annotations

import argparse
import sys

from repro.configs import registry as creg
from repro.launch.mesh import make_mesh
from repro.runtime.fault_tolerance import PreemptionGuard, run_supervised
from repro.train.trainer import TrainerConfig, train


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (smoke) config of the family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="runs/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mesh", default="1x1",
                    help="AxB -> (data, model) mesh over host devices")
    ap.add_argument("--supervise", action="store_true")
    args = ap.parse_args()

    cfg = creg.reduced(args.arch) if args.reduced else creg.get(args.arch)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    tcfg = TrainerConfig(seq=args.seq, global_batch=args.batch,
                         total_steps=args.steps, ckpt_every=args.ckpt_every,
                         ckpt_dir=args.ckpt_dir,
                         microbatches=args.microbatches)

    guard = PreemptionGuard().install()

    def run_once() -> int:
        return train(cfg, mesh, tcfg, guard=guard).exit_code

    if args.supervise:
        return run_supervised(run_once)
    return run_once()


if __name__ == "__main__":
    sys.exit(main())
