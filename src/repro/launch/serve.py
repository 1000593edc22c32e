"""Serving driver: prefill + batched decode against the KV cache.

Three decode modes over the same reduced model (DESIGN.md §13):

* legacy (default) — host Python loop, one jit dispatch per token.  Kept as
  the parity oracle: greedy scan mode must reproduce its tokens bit for bit.
* ``--scan`` — the serving engine's ``lax.scan``-compiled decode: the whole
  generation is one compiled program (greedy or ``--temperature`` sampling).
* ``--continuous`` — slot-based continuous batching via
  :class:`repro.serve.ServeEngine`: ``--requests`` sequences stream through
  ``--batch`` slots, finished slots refilled from the admission queue with
  zero recompilation.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --batch 4 --prompt-len 32 --gen 32 --scan --check

Token accounting is identical across modes: prefill is charged the ``b*p``
prompt tokens *and* samples the first generated token (so generated totals
are ``b*g``); decode is charged the remaining ``b*(g-1)``.  All timings use
``time.perf_counter()``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_arch
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.obs import TelemetrySink
from repro.obs import tracing as obs_tracing_lib
from repro.serve import (
    ServeConfig,
    ServeEngine,
    init_decode_state,
    make_decode_fn,
    run_scan,
)


def build_model(arch: str, seed: int):
    cfg = get_arch(arch).model.reduced(param_dtype="float32", dtype="float32", remat=False)
    params = T.init_params(jax.random.key(seed), cfg)
    return cfg, params


def _prefill_fn(cfg, b, p):
    @jax.jit
    def prefill(params, tokens, caches):
        positions = jnp.broadcast_to(jnp.arange(p)[None], (b, p))
        if cfg.pos_style == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, b, p))
        hidden, caches, _ = T.forward(cfg, params, tokens, positions, caches)
        return T.logits_from_hidden(cfg, params, hidden[:, -1:]), caches

    return prefill


def run_legacy(cfg, params, prompts, gen: int):
    """Host-loop greedy decode — the parity oracle.

    -> (tokens (B, gen), {"t_prefill": s, "t_decode": s})."""
    b, p = prompts.shape
    prefill = _prefill_fn(cfg, b, p)
    decode = jax.jit(lambda prm, tok, c: T.decode_step(cfg, prm, tok, c))

    caches = T.init_caches(cfg, b, p + gen)
    t0 = time.perf_counter()
    logits, caches = prefill(params, prompts, caches)
    toks = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(jnp.int32)
    jax.block_until_ready(toks)  # first generated token belongs to prefill
    t_prefill = time.perf_counter() - t0

    out = [toks]
    t0 = time.perf_counter()
    for _ in range(gen - 1):
        logits, caches = decode(params, toks, caches)
        toks = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(jnp.int32)
        out.append(toks)
    jax.block_until_ready(toks)
    t_decode = time.perf_counter() - t0
    gen_toks = np.asarray(jnp.concatenate(out, axis=1))
    return gen_toks, {"t_prefill": t_prefill, "t_decode": t_decode}


def run_scan_mode(cfg, params, prompts, gen: int, temperature: float = 0.0,
                  use_flash: bool = False, seed: int = 0):
    """Engine scan decode: batch prefill into per-slot caches, then the whole
    generation as one compiled ``lax.scan``.

    -> (tokens (B, gen), {"t_prefill": s, "t_decode": s})."""
    b, p = prompts.shape
    scfg = ServeConfig(batch=b, cache_len=p + gen, max_new=gen,
                       temperature=temperature, use_flash=use_flash)
    prefill = _prefill_fn(cfg, b, p)
    decode_fn = make_decode_fn(cfg, scfg)
    scan = jax.jit(lambda prm, s: run_scan(decode_fn, prm, s, gen - 1))

    state = init_decode_state(cfg, scfg, jax.random.key(seed))
    caches = T.init_caches(cfg, b, p + gen, per_slot=True)
    t0 = time.perf_counter()
    logits, caches = prefill(params, prompts, caches)
    from repro.serve.sampling import sample_tokens

    tok0, keys = jax.jit(sample_tokens, static_argnums=2)(
        logits, state.sample_keys, temperature
    )
    jax.block_until_ready(tok0)
    t_prefill = time.perf_counter() - t0

    state = dataclasses.replace(
        state,
        caches=caches,
        last_tok=tok0[:, None],
        out_tokens=state.out_tokens.at[:, 0].set(tok0),
        n_gen=jnp.ones((b,), jnp.int32),
        gen_target=jnp.full((b,), gen, jnp.int32),
        active=jnp.ones((b,), bool),
        seq_ids=jnp.arange(b, dtype=jnp.int32),
        sample_keys=keys,
    )
    t0 = time.perf_counter()
    state = scan(params, state)
    jax.block_until_ready(state.out_tokens)
    t_decode = time.perf_counter() - t0
    return np.asarray(state.out_tokens), {"t_prefill": t_prefill, "t_decode": t_decode}


def run_continuous(cfg, params, prompts, budgets, batch: int,
                   temperature: float = 0.0, decode_chunk: int = 8,
                   use_flash: bool = False, seed: int = 0, telemetry=None):
    """Continuous batching: stream len(prompts) requests through ``batch``
    slots.  -> (finished list, {"t_total": s, "tokens": n, "compiles": {...}}).

    ``telemetry`` (a :class:`repro.obs.TelemetrySink`) records the TTFT /
    per-chunk tok/s / occupancy / queue-depth series (DESIGN.md §14)."""
    n, p = prompts.shape
    gmax = int(max(budgets))
    scfg = ServeConfig(batch=batch, cache_len=p + gmax, max_new=gmax,
                       temperature=temperature, decode_chunk=decode_chunk,
                       use_flash=use_flash)
    eng = ServeEngine(cfg, scfg, params, prompt_len=p, key=jax.random.key(seed),
                      telemetry=telemetry)
    t0 = time.perf_counter()
    for i in range(n):
        eng.submit(np.asarray(prompts[i]), int(budgets[i]))
    finished = eng.run()
    t_total = time.perf_counter() - t0
    tokens = sum(len(f.tokens) for f in finished)
    return finished, {"t_total": t_total, "tokens": tokens,
                      "compiles": eng.compile_counts()}


def serve(args):
    cfg, params = build_model(args.arch, args.seed)
    b, p, g = args.batch, args.prompt_len, args.gen
    prompts = jax.random.randint(jax.random.key(1), (b, p), 0, cfg.vocab_size, jnp.int32)
    print(f"arch={args.arch} (reduced) batch={b} prompt={p} gen={g}")

    stack = contextlib.ExitStack()
    sink = None
    telemetry_path = getattr(args, "telemetry", None)
    with stack:
        stack.enter_context(
            obs_tracing_lib.trace(getattr(args, "profile_dir", None))
        )
        if telemetry_path:
            sink = stack.enter_context(TelemetrySink(telemetry_path))
            sink.write_manifest(
                config={"arch": args.arch, "batch": b, "prompt_len": p,
                        "gen": g, "temperature": args.temperature,
                        "use_flash": bool(args.flash), "seed": args.seed},
                extra={"mode": "serve"},
            )

        if args.continuous:
            n = args.requests or 2 * b
            all_prompts = jax.random.randint(
                jax.random.key(1), (n, p), 0, cfg.vocab_size, jnp.int32
            )
            rng = np.random.default_rng(args.seed)
            budgets = rng.integers(max(1, g // 4), g + 1, size=n) if args.mixed \
                else np.full(n, g)
            finished, stats = run_continuous(
                cfg, params, all_prompts, budgets, b,
                temperature=args.temperature, use_flash=args.flash,
                seed=args.seed, telemetry=sink,
            )
            print(f"continuous: {len(finished)} seqs, {stats['tokens']} generated "
                  f"tokens in {stats['t_total']*1e3:.1f} ms "
                  f"({stats['tokens']/stats['t_total']:,.0f} tok/s aggregate)")
            print(f"compiled programs: {stats['compiles']}")
            if sink is not None:
                print(f"telemetry -> {telemetry_path} (render with "
                      f"`python -m repro.analysis.report {telemetry_path}`)")
            return finished

        if args.scan:
            gen_toks, t = run_scan_mode(
                cfg, params, prompts, g, temperature=args.temperature,
                use_flash=args.flash, seed=args.seed,
            )
            mode = "scan"
        else:
            if args.temperature:
                raise SystemExit("--temperature requires --scan or --continuous "
                                 "(the legacy oracle is greedy-only)")
            gen_toks, t = run_legacy(cfg, params, prompts, g)
            mode = "legacy"

        if sink is not None:
            # batch modes have no admission queue — one summary event
            sink.emit("serve_summary", mode=mode, t_prefill_s=t["t_prefill"],
                      t_decode_s=t["t_decode"], tokens=b * g,
                      decode_tok_s=b * (g - 1) / max(t["t_decode"], 1e-9))
            print(f"telemetry -> {telemetry_path}")

    print(f"prefill: {t['t_prefill']*1e3:.1f} ms "
          f"({b*p/t['t_prefill']:,.0f} prompt tok/s, +{b} sampled)")
    print(f"decode[{mode}]: {t['t_decode']*1e3:.1f} ms "
          f"({b*(g-1)/max(t['t_decode'],1e-9):,.0f} tok/s)")
    print(f"generated total: {b*g} tokens")
    print("sample tokens:", gen_toks[0, :16].tolist())

    if args.check:
        if args.temperature:
            raise SystemExit("--check compares against the greedy oracle; "
                             "drop --temperature")
        oracle, _ = run_legacy(cfg, params, prompts, g)
        if not (gen_toks == oracle).all():
            raise SystemExit("parity FAILED: scan tokens != legacy tokens")
        print("parity OK: scan tokens bit-identical to legacy loop")
    return gen_toks


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_NAMES, default="smollm-360m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scan", action="store_true",
                    help="scan-compiled decode (serving engine)")
    ap.add_argument("--continuous", action="store_true",
                    help="slot-based continuous batching")
    ap.add_argument("--requests", type=int, default=0,
                    help="continuous mode: total requests (default 2*batch)")
    ap.add_argument("--mixed", action="store_true",
                    help="continuous mode: mixed generation budgets")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--flash", action="store_true",
                    help="route decode attention through the Pallas flash-decode kernel")
    ap.add_argument("--check", action="store_true",
                    help="assert scan tokens match the legacy oracle")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write JSONL telemetry to PATH (manifest + TTFT / "
                         "per-chunk tok/s / occupancy / queue-depth series "
                         "in --continuous mode, DESIGN.md §14)")
    ap.add_argument("--profile-dir", default=None, metavar="PATH",
                    help="capture a jax.profiler trace of the run into PATH "
                         "(TensorBoard-loadable)")
    args = ap.parse_args()
    use_compile_cache()
    serve(args)


if __name__ == "__main__":
    main()
