#!/usr/bin/env python3
"""Run one cell over the values of one parameter of its configuration or
traffic mix and print a table: how a deployment's size (lanes, callers, batch)
was found, and how a later ``benchmark`` issue finds a knee again.

    python benchmark/sweep.py --workload transformer-base.generate \\
        --param config.serving.lanes --values 32,64,128 --seconds 5

One process per value, one after another (a chip belongs to one process; this
parent never imports jax). Each child is ``run.py --set <param>=<value>``, so
its line carries "overrides" and is never mistaken for a result. Columns: the
cell's end-to-end metrics, the peak memory, and the driver's ``notes`` named
with ``--notes``. With ``--out`` the table is also written to a file.
"""
import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--param", required=True,
                    help="config.<path> or traffic.<path>")
    ap.add_argument("--values", required=True, help="comma list of JSON values")
    ap.add_argument("--also", action="append", default=[], metavar="K=V",
                    help="a fixed override applied to every run")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--notes", default="",
                    help="comma list of the line's notes to show")
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    rows, columns = [], []
    for value in args.values.split(","):
        cmd = [sys.executable, os.path.join(HERE, "run.py"),
               "--workload", args.workload, "--seed", str(args.seed),
               "--seconds", str(args.seconds), "--trace", str(args.trace),
               "--set", "%s=%s" % (args.param, value)]
        for pair in args.also:
            cmd += ["--set", pair]
        if args.rehearse_cpu:
            cmd.append("--rehearse-cpu")
        proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True)
        line = None
        for text in proc.stdout.splitlines():
            text = text.partition("REHEARSAL (not a result): ")[2] or text
            if text.startswith("{"):
                line = json.loads(text)
        if proc.returncode != 0 or line is None:
            rows.append((value, None, "exit %d" % proc.returncode))
            continue
        cells = {k: v["value"] for k, v in line["metrics"].items()}
        cells["memory_peak_gb"] = line["device"]["memory_peak_bytes"] / 1e9
        for name in filter(None, args.notes.split(",")):
            cells[name] = line["notes"].get(name)
        cells["correct"] = line["correct"]
        columns += [c for c in cells if c not in columns]
        rows.append((value, cells, None))

    out = ["| %s | %s |" % (args.param, " | ".join(columns)),
           "|" + "---|" * (len(columns) + 1)]
    for value, cells, error in rows:
        if cells is None:
            out.append("| %s | %s |" % (value, error))
            continue
        fmt = lambda v: "%.4g" % v if isinstance(v, float) else str(v)
        out.append("| %s | %s |" % (value, " | ".join(
            fmt(cells.get(c)) for c in columns)))
    table = "\n".join(out)
    print(table)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(table + "\n")
    return 0 if all(c is not None for _, c, _ in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
