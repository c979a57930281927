"""How far each gradient leaf of a train cell's check lies from the plain
reference, beside the yardsticks a rule can hold it to.  Not part of a
benchmark run: the record a limit of `correct` is set from (PERF.md
section 6), kept here so that it can be read again.

    python3 benchmarks/tools/leaf_margins.py outer <tag> <seed> [<seed> ...]
        one process a seed, in turn (a chip belongs to one process)
    python3 benchmarks/tools/leaf_margins.py inner <tag> <seed>
        the benchmark's own run of the cell at that seed; then, from the
        state its check left, one more step of the job's train step on
        each of the first task's next batches, and for every leaf the
        norms of: the reference's gradient (`want`), the step's error,
        the error of the reference with its tower in the stated type
        (bfloat16) and in the control's (float8_e4m3fn), the step against
        the bfloat16 one, and the leaf's sampling noise from eight parts
        of the batch

        of the batch; beside them the step the sample landed on, the
        losses, the three cosines of all leaves, the optimizer's worst
        `excess` and the whole gradient's norm.  Sample 0 is the run's
        own check
    python3 benchmarks/tools/leaf_margins.py table <tag> [<tag> ...]
        the tags' samples, one row a landing (the step the run's own
        check fell on): each term of `correct` as a share of its limit,
        worst and median, the cosine both against the constant
        `GRAD_COSINE_MIN` and against the twin's (`cosine_floor`), and
        the control in the step's place beside it (PERF.md section 6,
        PR 28)

The landing follows the window, the rate being the program's: the check
falls on step 8 x (2 warm-up tasks + the window's tasks) + 1.  At the
parent's 2.815 s a task MARGINS_SECONDS 32 | 38 | 44 | 55 close the window
after 12 | 14 | 16 | 20 tasks and land on step 113 | 129 | 145 | 177, the
three further batches on the three steps after.

Written to chiprun_out/margins_<tag>/<seed>.json, a line a seed to
chiprun_out/margins_<tag>.jsonl.  DeepFM cells only (it reads
`reference/deepfm.py`'s parts).  Environment: MARGINS_CELL, MARGINS_BATCHES
(3), MARGINS_SECONDS (32), MARGINS_BUDGET_S (outer stops starting runs),
MARGINS_ROOT and MARGINS_CPU (the CPU rehearsal at a tiny size).
"""

import glob
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
OUT = os.path.join(ROOT, "chiprun_out")
CELL = os.environ.get("MARGINS_CELL", "deepfm-criteo-kaggle.train-stream")
BATCHES = int(os.environ.get("MARGINS_BATCHES", "3"))
SECONDS = os.environ.get("MARGINS_SECONDS", "32")
TYPES = {"stated": "bfloat16", "control": "float8_e4m3fn"}


def outer(tag, seeds):
    started = time.time()
    budget = float(os.environ.get("MARGINS_BUDGET_S", "1e9"))
    os.makedirs(os.path.join(OUT, "margins_" + tag), exist_ok=True)
    for seed in seeds:
        if time.time() - started > budget:
            print("budget reached before seed", seed, flush=True)
            break
        t0 = time.time()
        try:
            done = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "inner", tag,
                 str(seed)],
                cwd=ROOT, capture_output=True, text=True,
                timeout=float(os.environ.get("MARGINS_TIMEOUT", "900")),
            )
            rc, out, err = done.returncode, done.stdout, done.stderr
        except subprocess.TimeoutExpired as exc:
            text = lambda b: b.decode(errors="replace") if isinstance(
                b, bytes) else (b or "")
            rc, out, err = 124, text(exc.stdout), text(exc.stderr)
        base = os.path.join(OUT, "margins_" + tag, str(seed))
        with open(base + ".out", "w") as f:
            f.write(out)
        with open(base + ".err", "w") as f:
            f.write(err[-20000:])
        try:
            line = json.loads(out.strip().splitlines()[-1])
        except (ValueError, IndexError):
            line = {}
        rec = {
            "seed": seed, "rc": rc, "wall_s": round(time.time() - t0, 1),
            "correct": line.get("correct"),
            "metrics": {
                k: v["value"] for k, v in line.get("metrics", {}).items()
            },
            "check": [t for t in out.splitlines() if "check:" in t],
        }
        with open(os.path.join(OUT, f"margins_{tag}.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps({k: rec[k] for k in rec if k != "check"}),
              flush=True)
        for text in rec["check"]:
            print("   ", text[:1200], flush=True)
        if rc:
            print(err[-1500:], flush=True)


def inner(tag, seed):
    sys.path.insert(0, ROOT)
    from benchmarks import run as bench_run   # stamps the process's start
    import numpy as np

    from benchmarks import adam_check, datagen, manifest
    from benchmarks.drivers import train
    from benchmarks.reference import deepfm

    root = os.environ.get("MARGINS_ROOT")
    if os.environ.get("MARGINS_CPU"):
        import jax

        train.preflight = lambda cell: {
            "platform": "cpu-rehearsal", "kind": "TPU v5 lite",
            "count": len(jax.devices()),
        }
        train.live_bytes = lambda: [0] * len(jax.devices())
        train.memory_peak_bytes = lambda live: {
            "peak": 1, "peak_live": 1, "live": 0, "scratch": 1,
        }
    if root:
        real_load, real_resolve = manifest.load_manifest, manifest.resolve_cell
        manifest.load_manifest = lambda r=root: real_load(r)
        manifest.resolve_cell = lambda m, w, r=root: real_resolve(m, w, r)
    original = train.check_train_step
    samples = []

    def norm(x):
        return float(np.linalg.norm(np.asarray(x, np.float64).ravel()))

    def f32(tree):
        return {k: np.asarray(v, np.float32) for k, v in tree.items()}

    def record(config, params, features, labels, got, step, batch, loss,
               optimizer, seconds):
        """One sample: the step's gradient `got` on `params` and this
        batch beside the reference's, its twins' and the noise."""
        t1 = time.perf_counter()
        want_loss, want = deepfm.loss_and_grads(
            params, features, labels, config
        )
        want = f32(want)
        t2 = time.perf_counter()
        twins, twin_loss = {}, {}
        for name, kind in TYPES.items():
            twin_loss[name], grads = deepfm.loss_and_grads(
                params, features, labels, config, tower=kind
            )
            twins[name] = f32(grads)
        t3 = time.perf_counter()
        noise = train.sampling_noise(
            deepfm, params, features, labels, config, want
        )
        t4 = time.perf_counter()
        # the rule itself, on the step and on the control in its place
        shares = {
            name: train.leaf_shares(
                deepfm, params, features, labels, config, want, grads,
                twin=twins["stated"],
            ) for name, grads in (("step", got),
                                  ("control", twins["control"]))
        }
        t5 = time.perf_counter()
        vectors = {"got": got, "stated": twins["stated"],
                   "control": twins["control"]}
        leaves = {}
        for k in want:
            off = {
                name: np.asarray(v[k], np.float64).ravel()
                - np.asarray(want[k], np.float64).ravel()
                for name, v in vectors.items()
            }
            along = np.asarray(want[k], np.float64).ravel()
            leaves[k] = {
                "size": int(want[k].size),
                "want": norm(want[k]), "got": norm(got[k]),
                "step_err": norm(off["got"]),
                "stated_err": norm(off["stated"]),
                "control_err": norm(off["control"]),
                "step_vs_stated": norm(off["got"] - off["stated"]),
                "control_vs_stated": norm(
                    off["control"] - off["stated"]
                ),
                # inner products of the three errors with each other
                # and with the reference's gradient: every projection
                # follows from them
                "dots": {
                    f"{a}.{b}": float(np.dot(x, y))
                    for a, x in list(off.items()) + [("want", along)]
                    for b, y in list(off.items()) + [("want", along)]
                    if a <= b
                },
                "noise": noise[k],
            }
            if want[k].size == 1:
                leaves[k]["values"] = {
                    "want": float(along[0]),
                    **{name: float(along[0] + v[0])
                       for name, v in off.items()},
                }
        samples.append({
            "seed": seed, "batch": batch, "step": step,
            "loss": {"step": loss, "want": float(want_loss),
                     "stated": float(twin_loss["stated"]),
                     "control": float(twin_loss["control"])},
            "cosine": {
                "step": adam_check.cosine(got, want),
                "stated": adam_check.cosine(twins["stated"], want),
                "control": adam_check.cosine(twins["control"], want),
            },
            "optimizer": max(optimizer.values()),
            "grad_norm": float(np.sqrt(sum(
                leaf["want"] ** 2 for leaf in leaves.values()
            ))),
            "label_mean": float(np.mean(labels)),
            "seconds": {"step": seconds, "reference": t2 - t1,
                        "twins": t3 - t2, "noise": t4 - t3,
                        "shares": t5 - t4},
            "shares": shares,
            "leaves": leaves,
        })
        print(f"[margins] batch {batch} at step {step}: step "
              f"{seconds:.1f}s reference {t2 - t1:.1f}s twins "
              f"{t3 - t2:.1f}s noise {t4 - t3:.1f}s", flush=True)
        out_dir = os.path.join(OUT, "margins_" + tag)
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"{seed}.json"), "w") as f:
            json.dump(samples, f)

    def patched(cell, window, first_records):
        config, size = cell.config, cell.traffic["minibatch_size"]
        # the run's own check is sample 0: what it handed
        # `check_gradient` is the state, the batch and the step's
        # gradient it judged
        seen = {}
        real_check = train.check_gradient

        def seeing(reference, params, features, labels, config, want, got):
            seen.update(params=params, features=features, labels=labels,
                        got=got)
            return real_check(reference, params, features, labels, config,
                              want, got)

        train.check_gradient = seeing
        t0 = time.perf_counter()
        try:
            result = original(cell, window, first_records)
        finally:
            train.check_gradient = real_check
        record(config, seen["params"], seen["features"], seen["labels"],
               seen["got"], result["step"], 0, result["loss"],
               result["optimizer"], time.perf_counter() - t0)
        owner = window.worker.model_owner
        data = train.data_spec(cell)
        h = adam_check.hyper(config)
        for b in range(1, 1 + min(BATCHES, len(first_records) // size - 1)):
            t0 = time.perf_counter()
            batch = datagen.RECORD_PARSERS[data["format"]](
                first_records[b * size:(b + 1) * size], data
            )
            features, labels = batch["features"], batch["labels"]
            before = train.state_on_host(owner.state, deepfm, features, config)
            loss = float(owner.train_batch(batch))
            after = train.state_on_host(owner.state, deepfm, features, config)
            got = {
                k: adam_check.recovered_gradient(
                    before["mu"][k], after["mu"][k], h["b1"]
                ) for k in before["mu"]
            }
            record(config, before["params"], features, labels, got,
                   after["count"], b, loss,
                   train.optimizer_excess(before, after, got, h),
                   time.perf_counter() - t0)
        return result

    train.check_train_step = patched
    return bench_run.main([
        "--workload", CELL, "--seed", str(seed), "--seconds", SECONDS,
        "--trace", "0",
    ])


def worst(*values) -> float:
    """The largest; a NaN (the fp8 twin gives one now and then) is past
    every limit."""
    return max(float("inf") if v != v else v for v in values)


def terms(sample, eps) -> dict:
    """Each term of `correct` in one sample as a share of its limit (at
    most 1 passes), the step's and the control's in its place."""
    from benchmarks import adam_check
    from benchmarks.reference import deepfm

    cosine = sample["cosine"]
    least = {
        "constant": deepfm.GRAD_COSINE_MIN,
        "twin": adam_check.cosine_floor(
            cosine["stated"], deepfm.STATED_RATIO, eps
        ),
    }
    out = {
        "loss": abs(sample["loss"]["step"] - sample["loss"]["want"])
        / deepfm.LOSS_ATOL,
        "optimizer": sample["optimizer"],
        "leaf": worst(*sample["shares"]["step"].values()),
        "control leaf": worst(*sample["shares"]["control"].values()),
    }
    for rule, floor in least.items():
        for who, key in (("", "step"), ("control ", "control")):
            out[f"{who}cosine, {rule}"] = worst(
                (1.0 - cosine[key]) / (1.0 - floor)
            )
    return out


def table(tags):
    sys.path.insert(0, ROOT)
    import jax.numpy as jnp

    eps = float(jnp.finfo(TYPES["stated"]).eps)
    landings = {}
    for tag in tags:
        for path in sorted(glob.glob(
            os.path.join(OUT, "margins_" + tag, "*.json")
        )):
            with open(path) as f:
                samples = json.load(f)
            landings.setdefault(samples[0]["step"], []).append(samples)
    sound = ("loss", "optimizer", "leaf", "cosine, constant", "cosine, twin")
    control = ("control leaf", "control cosine, constant",
               "control cosine, twin")
    print("| landing | runs, samples, steps | "
          + " | ".join(f"{t}: worst, median" for t in sound) + " | "
          + " | ".join(f"{t}: smallest, median" for t in control)
          + " | sound samples failing: constant, twin | control samples "
          "passing: constant, twin | gradient's norm: smallest, median, "
          "largest | twin's cosine: smallest, median |")
    print("|" + "---|" * (6 + len(sound) + len(control)))
    for landing in sorted(landings):
        samples = [s for run in landings[landing] for s in run]
        read = [terms(s, eps) for s in samples]
        column = lambda name: [r[name] for r in read]
        cells = [
            f"{landing}",
            f"{len(landings[landing])}, {len(samples)}, "
            f"{min(s['step'] for s in samples)}-"
            f"{max(s['step'] for s in samples)}",
        ] + [
            f"{max(column(t)):.3g}, {statistics.median(column(t)):.3g}"
            for t in sound
        ] + [
            f"{min(column(t)):.3g}, {statistics.median(column(t)):.3g}"
            for t in control
        ]
        fails = lambda r, who, rule: not (
            r[f"{who}leaf"] <= 1 and r[f"{who}cosine, {rule}"] <= 1
            and (who or (r["loss"] <= 1 and r["optimizer"] <= 1))
        )
        cells.append(", ".join(
            str(sum(fails(r, "", rule) for r in read))
            for rule in ("constant", "twin")
        ))
        cells.append(", ".join(
            str(sum(not fails(r, "control ", rule) for r in read))
            for rule in ("constant", "twin")
        ))
        norms = [s["grad_norm"] for s in samples]
        cells.append(f"{min(norms):.3g}, {statistics.median(norms):.3g}, "
                     f"{max(norms):.3g}")
        twins = [s["cosine"]["stated"] for s in samples]
        cells.append(f"{min(twins):.6f}, {statistics.median(twins):.6f}")
        print("| " + " | ".join(cells) + " |")


if __name__ == "__main__":
    if sys.argv[1] == "outer":
        outer(sys.argv[2], [int(a) for a in sys.argv[3:]])
    elif sys.argv[1] == "table":
        table(sys.argv[2:])
    else:
        sys.exit(inner(sys.argv[2], int(sys.argv[3])))
