"""Readings that set a cell's numbers, taken on the chip at the cell's size.

    python bench/calibrate.py curves --workload <cell> --seeds 1 2 --strategies fl-dp3s fedavg
    python bench/calibrate.py limits --workload <cell> --seeds 11 12 13 ...

``curves`` runs each seed's federations (one lockstep batch per strategy)
for half the configuration's round limit, with no target, and prints the
held-out accuracy after every chunk: the curves the target accuracy is
chosen from.  With ``--write-target`` it writes the target into the
configuration's file: 90% of the lowest best accuracy that any of those
federations reached within half the round limit.

``limits`` runs, for each seed, one lockstep batch as the window runs it
(to the target or the limit) and prints the comparison's numbers for every
federation of the batch: the program against the plain reference (the
lower readings), the reference in bfloat16 put in the program's place (the
control), and planted faults in the program's place: the reference with
each client's batch cut to its first half (the mean taken over the rest),
the reference's eq.-14 kernel with two of round 1's clients exchanged, and
the program's accuracy reading with the test labels shifted by one sample
(an answer altered where it is produced).  Where the adapter offers a
one-federation ``accuracy``, it also reads the first chunk's accuracy once
per federation and by one ``jax.vmap`` of it over the batch, for
comparison.  A program step that returns its
state unchanged reads 1 on ``update_gap`` by construction and needs no
run.  ``checked`` is each number's largest over the federations that a
run with this seed would check.  Each result is one JSON line on standard
output.  The benchmark's own runs run none of this.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _setup():
    here = Path(__file__).resolve().parent
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != here]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # a program over the default 192 MiB limit would compile anew in every run
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(4 << 30)


def _deployment(model, cell, seed):
    import jax

    from bench.federations import derive_key

    return model.make_deployment(cell.cfg, jax.random.key(derive_key(seed, 0)))


def _memory_peak():
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices())


def curves(cell, seeds, strategies, lockstep, write_target=False):
    from bench import harness
    from bench.federations import Runner

    # no target, half the round limit: what the target's rule reads
    cfg = dict(cell.cfg, target_accuracy=2.0, max_rounds=int(cell.cfg["max_rounds"]) // 2)
    every = int(cfg["eval_every"])
    best = []  # each federation's best accuracy
    model = harness.load_module("models", cfg["model"], cell.root)
    for strategy in strategies:
        system = model.System(cfg, strategy)
        traffic = dict(cell.traffic, strategy=strategy, lockstep=lockstep)
        for seed in seeds:
            runner = Runner(system, cfg, traffic, _deployment(model, cell, seed), seed)
            accs = []
            orig = system.accuracy

            def spy(*a, **k):
                out = orig(*a, **k)
                accs.append([float(x) for x in (out if out.ndim else [out])])
                return out

            system.accuracy = spy
            t0 = time.perf_counter()
            runner.run_batch(0, snapshot=False)
            system.accuracy = orig
            best.extend(max(col) for col in zip(*accs))
            print(json.dumps({"mode": "curves", "workload": cell.name, "strategy": strategy,
                              "seed": seed, "lockstep": lockstep,
                              "seconds": time.perf_counter() - t0,
                              "memory_peak_bytes": _memory_peak(),
                              "every": every, "acc": accs}), flush=True)
    if write_target:
        # 90% of the slowest federation's best accuracy within half the round
        # limit: every calibration federation reaches it with room to spare
        target = math.floor(0.9 * min(best) * 100) / 100
        path = cell.root / "bench" / "configs" / f"{cfg['name']}.json"
        spec = json.loads(path.read_text())
        spec["target_accuracy"] = target
        path.write_text(json.dumps(spec, indent=2) + "\n")
        print(json.dumps({"mode": "target", "workload": cell.name, "target_accuracy": target,
                          "slowest_best": min(best), "federations": len(best)}), flush=True)


def _swap_clients(kernel, pair):
    """The eq.-14 kernel with two clients' rows and columns exchanged: a
    kernel attributed to the wrong clients (the first two of round 1's
    cohort)."""
    import numpy as np

    k = np.array(kernel, copy=True)
    i, j = (int(x) for x in pair)
    k[[i, j]] = k[[j, i]]
    k[:, [i, j]] = k[:, [j, i]]
    return k


def limits(cell, seeds):
    import jax
    import jax.numpy as jnp

    from bench import check, harness
    from bench.federations import Runner

    cfg = cell.cfg
    model = harness.load_module("models", cfg["model"], cell.root)
    ref = harness.load_module("references", cfg["reference"], cell.root)
    system = model.System(cfg, cell.traffic["strategy"])
    for seed in seeds:
        t0 = time.perf_counter()
        runner = Runner(system, cfg, cell.traffic, _deployment(model, cell, seed), seed)
        runner.run_batch(0)
        t1 = time.perf_counter()
        out = {"mode": "limits", "workload": cell.name, "seed": seed,
               "cohort_invalid": runner.invalid_rounds, "batch_s": t1 - t0}
        picked = harness.pick(runner, cell, seed)
        checked = {}
        for batch, slot in sorted(runner.snapshots):
            snap = runner.snapshot(batch, slot)
            t2 = time.perf_counter()
            want = harness.reference_run(ref, cfg, runner, batch, slot, snap)
            t3 = time.perf_counter()
            ctrl = harness.reference_run(ref, cfg, runner, batch, slot, snap, dtype=jnp.bfloat16)
            half = harness.reference_run(ref, cfg, runner, batch, slot, snap, keep=0.5)
            swapped = dict(want, kernel=_swap_clients(want["kernel"], snap["selected"][0][:2]))
            misread = dict(snap, judged_acc=float(system.accuracy(
                snap["judged_params"], runner.test_xs, jnp.roll(runner.test_ys, 1), 1)))
            rec = {
                "federation": [batch, slot],
                "program": check.compare(snap, want),
                "control_bf16": check.compare(ctrl, want),
                "fault_half_batch": check.compare(half, want),
                "fault_kernel_swap": check.compare(swapped, want),
                "fault_acc_misread": check.compare(misread, want),
                "program_loss": [float(x) for x in snap["loss"]],
                "reference_loss": [float(x) for x in want["loss"]],
                "control_loss": [float(x) for x in ctrl["loss"]],
                "judged_round": snap["judged_round"], "program_acc": snap["judged_acc"],
                "reference_acc": want["judged_acc"], "control_acc": ctrl["judged_acc"],
                "misread_acc": misread["judged_acc"], "reference_s": t3 - t2,
            }
            out.setdefault("federations", []).append(rec)
            if (batch, slot) in picked:
                for kind in ("program", "control_bf16", "fault_half_batch",
                             "fault_kernel_swap", "fault_acc_misread"):
                    into = checked.setdefault(kind, {})
                    for k, v in rec[kind].items():
                        into[k] = max(into.get(k, 0.0), v)
        out["checked"] = checked
        # the first chunk's params, read one federation at a time (as the
        # harness reads them) and by one vmapped call over the batch
        stacked = runner.snapshots[(0, 0)]["params"]
        one = getattr(model, "accuracy", None)
        if runner.lockstep > 1 and one is not None:
            out["first_chunk_acc"] = {
                "per_federation": [float(x) for x in system.accuracy(
                    stacked, runner.test_xs, runner.test_ys, runner.lockstep)],
                "vmapped": [float(x) for x in jax.vmap(one, in_axes=(0, None, None))(
                    stacked, runner.test_xs, runner.test_ys)]}
        out["memory_peak_bytes"] = _memory_peak()
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("curves", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--strategies", nargs="*", default=None)
    ap.add_argument("--lockstep", type=int, default=None)
    ap.add_argument("--write-target", action="store_true",
                    help="write the chosen target accuracy into the configuration file")
    args = ap.parse_args(argv)
    _setup()
    from bench import harness

    cell = harness.load_cell(args.workload)
    if args.mode == "curves":
        curves(cell, args.seeds, args.strategies or [cell.traffic["strategy"]],
               args.lockstep or int(cell.traffic["lockstep"]), args.write_target)
    else:
        limits(cell, args.seeds)


if __name__ == "__main__":
    main()
