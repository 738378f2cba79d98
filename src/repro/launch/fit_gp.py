"""SBV GP fitting driver — the paper's main entry point.

    PYTHONPATH=src python -m repro.launch.fit_gp --n 20000 --d 10 \
        --blocks 400 --m 60 --workers 1 --dataset synthetic

Datasets: synthetic (paper §6.1), satdrag (§6.2-like), metarvm (§6.3-like).
``--workers k`` runs the distributed likelihood over a k-device mesh
(CPU devices stand in for the paper's MPI ranks).

Out-of-core (docs/streaming.md): ``--store DIR`` fits straight from an
``ArrayStore`` directory instead of materializing the dataset in RAM;
``--write-store DIR`` generates the synthetic dataset chunk-by-chunk into
a store first (then fits from it), and ``--stream-chunk`` bounds the rows
held on host per pass:

    PYTHONPATH=src python -m repro.launch.fit_gp --dataset synthetic \
        --n 1000000 --write-store /tmp/sbv-1m --stream-chunk 131072

Multi-process (docs/streaming.md "multi-host construction"):
``--distributed-hosts K`` re-launches this driver as K rank processes
connected through ``jax.distributed`` — each rank owns one partition of
the store, builds its share of the block structure (k-means all-reduce +
halo NNS exchange), spools only its own pieces, and joins the others in
a lockstep per-chunk loss/grad all-reduce. The parent merges the
per-rank ``--result-json`` files. Heavy imports stay INSIDE ``main``:
a rank must call ``jax.distributed.initialize`` before anything
initializes the JAX backend, so the module must import clean.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np


def load_dataset(name: str, n: int, seed: int, outputs: int = 1):
    from repro.data.gp_sim import (metarvm_dataset, metarvm_field_dataset,
                                   paper_synthetic, satellite_drag_like)

    if outputs > 1:
        if name != "metarvm":
            raise SystemExit("--outputs > 1 requires --dataset metarvm "
                             "(the multi-output field variant)")
        return metarvm_field_dataset(seed, n, p=outputs)
    if name == "synthetic":
        x, y, params = paper_synthetic(seed, n)
        return x, y
    if name == "satdrag":
        return satellite_drag_like(seed, n)
    if name == "metarvm":
        return metarvm_dataset(seed, n)
    raise ValueError(name)


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="synthetic",
                    choices=["synthetic", "satdrag", "metarvm"])
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--outputs", type=int, default=1, metavar="P",
                    help="emulate P outputs jointly through the shared-"
                         "structure multi-output fit (docs/multioutput.md); "
                         "metarvm only — snapshots the epidemic trajectory "
                         "at P evenly spaced days")
    ap.add_argument("--blocks", type=int, default=400)
    ap.add_argument("--m", type=int, default=60)
    ap.add_argument("--m-pred", type=int, default=120)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--inner-steps", type=int, default=40)
    ap.add_argument("--outer-rounds", type=int, default=2)
    ap.add_argument("--backend", default="ref",
                    choices=["ref", "pallas", "auto"])
    ap.add_argument("--test-frac", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="fit from an existing ArrayStore directory "
                         "(out-of-core; --n/--dataset are ignored)")
    ap.add_argument("--write-store", default=None, metavar="DIR",
                    help="generate the dataset chunk-by-chunk into a new "
                         "store at DIR, then fit from it")
    ap.add_argument("--stream-chunk", type=int, default=None,
                    help="max dataset rows held on host per streaming pass "
                         "(implies the out-of-core fit path)")
    ap.add_argument("--device-cache-mb", type=float, default=None,
                    help="HBM budget (MB) for the streaming fit's "
                         "device-resident spool tier; default sizes it from "
                         "free device memory, 0 disables the cache")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="disk-tier spool pieces staged ahead of the device "
                         "by the H2D producer thread (0 = synchronous reads)")
    ap.add_argument("--precision", default=None,
                    choices=["bf16", "f32", "f64"],
                    help="covariance-assembly ladder tier (docs/precision.md);"
                         " in-core fits probe per bucket and demote rungs "
                         "that exceed the tier's error budget")
    ap.add_argument("--autotune", action="store_true",
                    help="measure candidate (buckets x precision) shapes on "
                         "a dataset sample first and fit with the winner "
                         "(docs/precision.md); with --tuning-record PATH the "
                         "measured record is persisted there")
    ap.add_argument("--tuning-record", default=None, metavar="PATH",
                    help="persisted autotuner record: with --autotune the "
                         "save destination, otherwise loaded to start the "
                         "fit pre-tuned")
    ap.add_argument("--distributed-hosts", type=int, default=0, metavar="K",
                    help="spawn K rank processes over jax.distributed and "
                         "run the multi-host streaming fit (requires the "
                         "out-of-core path: --store/--write-store)")
    ap.add_argument("--result-json", default=None, metavar="PATH",
                    help="write the run summary as JSON (rank processes "
                         "write PATH.rank<r>; the parent merges them)")
    return ap


def write_store(args):
    """Chunked synthetic generation into a store (bounded RAM)."""
    from repro.data.store import ArrayStore

    # The synthetic dataset is a GP DRAW, so its chunks must come from one
    # shared function realization (paper_synthetic_chunks fixes the RFF
    # weights once); satdrag/metarvm are deterministic simulators of x,
    # so re-seeding their x-sampling per chunk is sound.
    gen_rows = 65536
    if args.dataset == "synthetic":
        from repro.data.gp_sim import paper_synthetic_chunks

        chunks = paper_synthetic_chunks(args.seed, args.n, gen_rows=gen_rows)
    else:
        def _sim_chunks():
            done, part = 0, 0
            while done < args.n:
                k = min(args.n - done, gen_rows)
                yield load_dataset(args.dataset, k, args.seed + part)
                done += k
                part += 1

        chunks = _sim_chunks()
    first_x, first_y = next(chunks)
    with ArrayStore.create(args.write_store, first_x.shape[1]) as w:
        w.append(first_x, first_y)
        for xp, yp in chunks:
            w.append(xp, yp)
    store = ArrayStore(args.write_store)
    print(f"[fit_gp] wrote store {args.write_store}: "
          f"{store.n_rows} rows x {store.d} dims, {store.n_shards} shards")
    return store


# -- multi-host launch ------------------------------------------------------


def _spawn_hosts(args) -> dict:
    """Parent mode: launch K rank copies of this driver and merge results.

    The parent only prepares the store and babysits processes — it never
    touches jax.distributed, so heavy imports are safe here. On an
    accelerator host it refuses instead (one process per chip)."""
    from repro.multihost import require_cpu_ranks

    require_cpu_ranks()
    if args.write_store:
        write_store(args)
        store_dir = args.write_store
    elif args.store:
        store_dir = args.store
    else:
        raise SystemExit("--distributed-hosts requires --store or "
                         "--write-store (ranks share one store directory)")

    from repro.multihost import ENV_COORD, ENV_NPROCS, ENV_RANK

    k = int(args.distributed_hosts)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    child_argv = [sys.executable, "-m", "repro.launch.fit_gp",
                  "--store", store_dir,
                  "--blocks", str(args.blocks), "--m", str(args.m),
                  "--inner-steps", str(args.inner_steps),
                  "--outer-rounds", str(args.outer_rounds),
                  "--backend", args.backend, "--seed", str(args.seed),
                  "--prefetch", str(args.prefetch)]
    if args.stream_chunk:
        child_argv += ["--stream-chunk", str(args.stream_chunk)]
    if args.precision:
        child_argv += ["--precision", args.precision]
    if args.device_cache_mb is not None:
        child_argv += ["--device-cache-mb", str(args.device_cache_mb)]
    if args.result_json:
        child_argv += ["--result-json", args.result_json]

    env = dict(os.environ)
    env.setdefault("PYTHONPATH",
                   os.path.dirname(os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__)))))
    procs = []
    for r in range(k):
        e = dict(env)
        e[ENV_RANK] = str(r)
        e[ENV_NPROCS] = str(k)
        e[ENV_COORD] = f"127.0.0.1:{port}"
        procs.append(subprocess.Popen(child_argv, env=e,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    failed = False
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=3600)
        text = out.decode(errors="replace")
        for line in text.splitlines():
            print(f"[rank {r}] {line}")
        if p.returncode != 0:
            print(f"[fit_gp] rank {r} exited with {p.returncode}")
            failed = True
    if failed:
        raise SystemExit("multi-host fit failed — see rank logs above")

    merged = None
    if args.result_json:
        ranks = []
        for r in range(k):
            with open(f"{args.result_json}.rank{r}") as f:
                ranks.append(json.load(f))
        nlls = [rk["nll"] for rk in ranks]
        merged = {"n_hosts": k, "nll": nlls[0],
                  "max_nll_spread": float(max(nlls) - min(nlls)),
                  "ranks": ranks}
        with open(args.result_json, "w") as f:
            json.dump(merged, f, indent=1)
        print(f"[fit_gp] merged {k} rank results -> {args.result_json} "
              f"(nll={nlls[0]:.9f}, spread={merged['max_nll_spread']:.3g})")
    return merged or {"n_hosts": k}


def _run_rank(ctx, args) -> dict:
    """Child mode: one rank of the multi-host streaming fit.

    Ranks fit only (prediction stays a single-process concern for now)
    and report their partition telemetry + peak RSS so the launcher and
    the benchmarks can assert the per-host memory contract."""
    from repro.core.fit import fit_sbv
    from repro.core.pipeline import SBVConfig
    from repro.data.store import ArrayStore
    from repro.data.streaming import working_set_model
    from repro.memwatch import PeakRssSampler

    if not args.store:
        raise SystemExit("rank processes need --store")
    store = ArrayStore(args.store)
    cfg = SBVConfig(n_blocks=args.blocks, m=args.m, seed=args.seed)
    device_cache = (None if args.device_cache_mb is None
                    else int(args.device_cache_mb * 2**20))

    sampler = PeakRssSampler().start()
    t0 = time.time()
    res = fit_sbv(store, None, cfg, inner_steps=args.inner_steps,
                  outer_rounds=args.outer_rounds, backend=args.backend,
                  stream_chunk=args.stream_chunk, verbose=True,
                  device_cache=device_cache, prefetch=args.prefetch,
                  multihost=ctx, precision=args.precision)
    t_fit = time.time() - t0
    peak = sampler.stop()

    st = res.stream_stats
    ws = working_set_model(st, store.n_rows, store.d, args.m,
                           args.stream_chunk or store.n_rows)
    out = {
        "rank": ctx.rank, "n_hosts": ctx.size,
        "nll": float(res.history[-1][2]), "t_fit_s": t_fit,
        "sigma2": float(res.params.sigma2),
        "beta": np.asarray(res.params.beta).tolist(),
        "nugget": float(res.params.nugget),
        "peak_rss_bytes": peak,
        "working_set_bytes": int(ws["total"]),
        "stats": {key: v for key, v in st.items()
                  if isinstance(v, (int, float, str, bool))},
    }
    print(f"[fit_gp] rank {ctx.rank}/{ctx.size}: nll={out['nll']:.9f} "
          f"fit {t_fit:.1f}s, owned {st.get('owned_rows')}/{store.n_rows} "
          f"rows (+{st.get('halo_rows', 0)} halo), "
          f"exchange {st.get('exchange_bytes', 0) / 2**20:.1f}MB")
    if args.result_json:
        with open(f"{args.result_json}.rank{ctx.rank}", "w") as f:
            json.dump(out, f, indent=1)
    ctx.shutdown()
    return out


def main(argv=None):
    # Rank processes must connect BEFORE any import initializes the JAX
    # backend — repro.multihost imports jax lazily, so this is safe.
    from repro.multihost import MultihostContext

    ctx = MultihostContext.from_env()
    args = build_parser().parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.outputs > 1 and (args.store or args.write_store
                             or args.distributed_hosts):
        raise SystemExit("--outputs > 1 runs the in-core multi-output fit; "
                         "combine it with --stream-chunk for the streaming "
                         "path, not --store/--write-store/--distributed-hosts")

    if ctx is not None:
        return _run_rank(ctx, args), None
    if args.distributed_hosts and args.distributed_hosts > 1:
        return _spawn_hosts(args), None

    from repro.core.fit import fit_sbv
    from repro.core.pipeline import SBVConfig
    from repro.core.predict import predict_sbv

    store = None
    if args.store:
        from repro.data.store import ArrayStore

        store = ArrayStore(args.store)
    elif args.write_store:
        store = write_store(args)

    def _tune(x_t, y_t, cfg_t):
        """Resolve the tuning input: measure (--autotune) or load a
        persisted record (--tuning-record without --autotune)."""
        if args.autotune:
            from repro.tuning import autotune_loglik

            t_a = time.time()
            rec = autotune_loglik(x_t, y_t, cfg_t, backend=args.backend,
                                  save_dir=args.tuning_record, verbose=True)
            print(f"[fit_gp] autotune {time.time() - t_a:.1f}s -> "
                  f"buckets={rec.n_buckets} precision={rec.precision} "
                  f"stream-chunk={rec.stream_chunk}")
            return rec
        return args.tuning_record

    if store is not None:
        rng = np.random.default_rng(args.seed + 999)
        # Probe set: a bounded random row sample. The streaming fit trains
        # on every row, so this MSPE is in-sample — a surrogate sanity
        # check, not a generalization score.
        n_test = min(5000, max(1, int(store.n_rows * args.test_frac)))
        x_te, y_te = store.read_rows(
            rng.choice(store.n_rows, size=n_test, replace=False))
        y_te_c = y_te  # streaming path fits the raw observations
        mu_y = 0.0
        cfg = SBVConfig(n_blocks=args.blocks, m=args.m,
                        n_workers=args.workers, seed=args.seed)
        distributed = None
        if args.workers > 1:
            from repro.launch.mesh import make_worker_mesh

            distributed = (make_worker_mesh(args.workers), "workers")
        device_cache = (None if args.device_cache_mb is None
                        else int(args.device_cache_mb * 2**20))
        tuning = None
        if args.autotune or args.tuning_record:
            # Autotune on a bounded head sample of the store; the record's
            # stream_chunk recommendation still uses the FULL row count.
            if args.autotune:
                x_s, y_s = store.read_slice(0, min(store.n_rows, 20_000))
                tuning = _tune(x_s, y_s, cfg)
            else:
                tuning = _tune(None, None, cfg)

        t0 = time.time()
        res = fit_sbv(store, None, cfg, inner_steps=args.inner_steps,
                      outer_rounds=args.outer_rounds, backend=args.backend,
                      stream_chunk=args.stream_chunk, verbose=True,
                      distributed=distributed, device_cache=device_cache,
                      prefetch=args.prefetch, precision=args.precision,
                      tuning=tuning)
        t_fit = time.time() - t0
        beta = np.asarray(res.params.beta)
        st = res.stream_stats
        print(f"[fit_gp] streaming fit {store.n_rows} pts in {t_fit:.1f}s "
              f"({st['n_chunks']} chunks/round, "
              f"{st['device_cached_pieces']}/{st['n_pieces']} pieces "
              f"device-cached, {st['h2d_bytes_per_step'] / 2**20:.1f}MB "
              f"H2D/step); sigma2={float(res.params.sigma2):.4f}")
        print("[fit_gp] relevance 1/beta:", np.round(1.0 / beta, 3))

        t0 = time.time()
        pred = predict_sbv(res.params, store, None, x_te, bs_pred=5,
                           m_pred=args.m_pred, chunk_size=4096,
                           stream_chunk=args.stream_chunk)
        t_pred = time.time() - t0
    else:
        x, y = load_dataset(args.dataset, args.n, args.seed,
                            outputs=args.outputs)
        n_test = int(y.shape[0] * args.test_frac)
        x_tr, y_tr = x[:-n_test], y[:-n_test]
        x_te, y_te = x[-n_test:], y[-n_test:]
        mu_y = y_tr.mean(axis=0)  # per-output centering (scalar when 1-D)
        y_tr_c, y_te_c = y_tr - mu_y, y_te - mu_y

        cfg = SBVConfig(n_blocks=args.blocks, m=args.m, n_workers=args.workers,
                        seed=args.seed)
        distributed = None
        if args.workers > 1:
            from repro.launch.mesh import make_worker_mesh

            mesh = make_worker_mesh(args.workers)
            distributed = (mesh, "workers")

        tuning = _tune(x_tr, y_tr_c, cfg) \
            if (args.autotune or args.tuning_record) else None

        t0 = time.time()
        res = fit_sbv(x_tr, y_tr_c, cfg, inner_steps=args.inner_steps,
                      outer_rounds=args.outer_rounds, backend=args.backend,
                      distributed=distributed, verbose=True,
                      stream_chunk=args.stream_chunk,
                      precision=args.precision, tuning=tuning)
        t_fit = time.time() - t0
        beta = np.asarray(res.params.beta)
        sigma2 = np.asarray(res.params.sigma2)
        nugget = np.asarray(res.params.nugget)
        if sigma2.ndim:  # multi-output: per-output vectors
            print(f"[fit_gp] fit {len(y_tr)} pts x {sigma2.size} outputs in "
                  f"{t_fit:.1f}s; sigma2={np.round(sigma2, 4)} "
                  f"tau2={float(res.params.tau2):.2e}")
        else:
            print(f"[fit_gp] fit {len(y_tr)} pts in {t_fit:.1f}s; "
                  f"sigma2={float(sigma2):.4f} nugget={float(nugget):.2e}")
        print("[fit_gp] relevance 1/beta:", np.round(1.0 / beta, 3))

        t0 = time.time()
        pred = predict_sbv(res.params, x_tr, y_tr_c, x_te,
                           bs_pred=5, m_pred=args.m_pred)
        t_pred = time.time() - t0
    mspe = float(np.mean((pred.mean - y_te_c) ** 2))
    denom = np.where(np.abs(y_te) > 1e-8, y_te, 1.0)
    rmspe = float(np.sqrt(np.mean(((pred.mean + mu_y - y_te) / denom) ** 2))) * 100
    cover = float(np.mean((y_te_c >= pred.ci_low) & (y_te_c <= pred.ci_high))) * 100
    print(f"[fit_gp] predict {n_test} pts in {t_pred:.1f}s: "
          f"MSPE={mspe:.5f} RMSPE={rmspe:.2f}% CI95-coverage={cover:.1f}%")
    if args.result_json:
        payload = {"nll": float(res.history[-1][2]), "t_fit_s": t_fit,
                   "t_predict_s": t_pred, "mspe": mspe, "rmspe_pct": rmspe,
                   "sigma2": np.asarray(res.params.sigma2).tolist(),
                   "beta": np.asarray(res.params.beta).tolist(),
                   "nugget": np.asarray(res.params.nugget).tolist()}
        with open(args.result_json, "w") as f:
            json.dump(payload, f, indent=1)
    return res, mspe


if __name__ == "__main__":
    main()
