"""Serving driver: paged continuous-batching scheduler (default) or the
lockstep fixed-batch baseline.

``--engine paged`` routes a stream of (possibly mixed-length) requests
through ``repro.serving.scheduler`` — paged KV-cache, admission on free
pages, chunked prefill, mid-flight eviction (DESIGN.md §Serving).
``--engine lockstep`` is the old fixed-batch loop kept as the benchmark
baseline: one contiguous prompt+decode cache per request, no admission
until the whole batch finishes. BOTH engines sample inside the jitted
decode step (``--sample greedy|temp``) — the per-token host ``argmax``
round-trip is gone.

    PYTHONPATH=src python -m repro.launch.serve --arch zamba2-7b --smoke \
        --prompt-lens 32,8,16 --decode-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import base
from repro.data.lm import SyntheticLM, SyntheticLMConfig
from repro.launch import compile_cache
from repro.models import registry
from repro.obs import trace as obs_trace
from repro.serving import paging
from repro.serving.scheduler import (Scheduler, ServeConfig, per_slot_keys,
                                     sample_tokens)


def make_prompts(cfg, prompt_lens, seed: int, prefix_len: int = 0):
    """Deterministic synthetic prompts, one per requested length. With
    ``prefix_len`` > 0 every prompt starts with the SAME ``prefix_len``
    tokens (a shared system prompt) — the workload ``--share-prefix``
    deduplicates into shared physical pages."""
    rows = len(prompt_lens) + (1 if prefix_len else 0)
    data = SyntheticLM(SyntheticLMConfig(
        cfg.vocab_size, prefix_len + max(prompt_lens), seed=seed))
    raw = data.batch(0, rows)["tokens"]
    prefix = np.asarray(raw[-1, :prefix_len], np.int32) \
        if prefix_len else np.zeros((0,), np.int32)
    return [np.concatenate([prefix, np.asarray(raw[i, :n], np.int32)])
            for i, n in enumerate(prompt_lens)]


# ------------------------------------------------------------- lockstep --
class LockstepEngine:
    """Fixed-batch baseline: pad every prompt to the longest, prefill the
    wave, decode until the WHOLE wave hits its token budget. A new wave
    starts only when the previous one has fully finished — the admission
    pathology continuous batching removes. Jitted steps are built once so
    benchmarks can warm the engine and time steady-state waves."""

    def __init__(self, cfg, params, *, sample: str = "greedy",
                 temperature: float = 1.0, batch: int = 4, seed: int = 0):
        self.cfg, self.params = cfg, params
        self.batch = batch
        self.key = jax.random.PRNGKey(seed)

        @jax.jit
        def prefill(params, cache, tokens, positions, key, frames=None):
            if cfg.is_encoder_decoder:       # whisper: encode + cross-KV
                cache = registry.prefill_cross_cache(params, cfg, frames,
                                                     cache)
            logits, _, cache = registry.apply_model(
                params, cfg, {"tokens": tokens,
                              "positions": registry.build_positions(
                                  cfg, positions)}, caches=cache)
            nxt = sample_tokens(logits[:, -1, :],
                                per_slot_keys(key, tokens.shape[0]),
                                sample, temperature)
            return nxt, cache

        @jax.jit
        def decode(params, cache, tokens, pos_scalar, key):
            b = tokens.shape[0]
            positions = registry.build_positions(
                cfg, jnp.broadcast_to(pos_scalar[None, None], (b, 1)))
            logits, cache = registry.decode_step(
                params, cfg, tokens[:, None], positions, cache)
            nxt = sample_tokens(logits[:, -1, :], per_slot_keys(key, b),
                                sample, temperature)
            return nxt, cache

        self._prefill, self._decode = prefill, decode

    def run(self, prompts, decode_tokens: int) -> dict:
        """Serve ``prompts``, ``decode_tokens`` new tokens each, in waves
        of ``self.batch``. Short prompts in a wave are right-padded by
        repeating their last token (the baseline is defined on
        equal-length waves)."""
        cfg = self.cfg
        waves = [list(range(i, min(i + self.batch, len(prompts))))
                 for i in range(0, len(prompts), self.batch)]
        plen = max(len(p) for p in prompts)
        cache_len = plen + decode_tokens
        outputs = {}
        t0 = time.time()
        for wi, wave in enumerate(waves):
            wb = len(wave)
            # per-(wave, step) call keys (the in-jit per-slot fold adds the
            # slot axis): without the wave component, temperature sampling
            # would replay identical draws in every wave
            wave_key = jax.random.fold_in(self.key, wi)
            toks = np.zeros((wb, plen), np.int32)
            for j, i in enumerate(wave):
                toks[j, :len(prompts[i])] = prompts[i]
                toks[j, len(prompts[i]):] = prompts[i][-1]
            cache = registry.init_cache(cfg, wb, cache_len)
            frames = None
            if cfg.is_encoder_decoder:       # stub audio frames (data.lm)
                frames = 0.02 * jax.random.normal(
                    jax.random.fold_in(self.key, 99),
                    (wb, cfg.source_positions, cfg.d_model), jnp.bfloat16)
            nxt, cache = self._prefill(
                self.params, cache, jnp.asarray(toks),
                jnp.broadcast_to(jnp.arange(plen)[None], (wb, plen)),
                jax.random.fold_in(wave_key, 0), frames)
            gen = [np.asarray(nxt)]
            for i in range(decode_tokens - 1):
                nxt, cache = self._decode(self.params, cache, nxt,
                                          jnp.int32(plen + i),
                                          jax.random.fold_in(wave_key,
                                                             i + 1))
                gen.append(np.asarray(nxt))
            jax.block_until_ready(nxt)
            stacked = np.stack(gen, axis=1)                # (wb, decode)
            for j, i in enumerate(wave):
                outputs[i] = stacked[j]
        wall = time.time() - t0
        total = decode_tokens * len(prompts)
        return {"outputs": outputs, "wall_s": wall,
                "tokens_per_s": total / max(wall, 1e-9),
                "decode_steps": decode_tokens * len(waves)}


def run_lockstep(cfg, params, prompts, decode_tokens: int, *,
                 sample: str = "greedy", temperature: float = 1.0,
                 batch: int = 4, seed: int = 0) -> dict:
    return LockstepEngine(cfg, params, sample=sample,
                          temperature=temperature, batch=batch,
                          seed=seed).run(prompts, decode_tokens)


# ---------------------------------------------------------------- paged --
def run_paged(cfg, params, prompts, decode_tokens: int, *,
              serve_cfg: ServeConfig) -> dict:
    sched = Scheduler(cfg, params, serve_cfg)
    rids = [sched.submit(p, decode_tokens) for p in prompts]
    t0 = time.time()
    finished = sched.run()
    wall = time.time() - t0
    total = decode_tokens * len(prompts)
    ttft = sorted(sched.ttft_s.values())
    queue = sorted(sched.ttft_queue_s.values())
    return {"outputs": {i: finished[r] for i, r in enumerate(rids)},
            "wall_s": wall, "tokens_per_s": total / max(wall, 1e-9),
            "decode_steps": sched.decode_steps,
            "prefill_chunks": sched.prefill_chunks,
            "peak_pages_in_use": sched.peak_pages_in_use,
            "final_pages_in_use": sched.pool.in_use,
            "page_bytes": paging.cache_page_bytes(sched.cache),
            "pages_alloc_events": sched.pages_alloc_events,
            "shared_page_hits": sched.shared_page_hits,
            "cow_forks": sched.cow_forks,
            "preemptions": sched.preemptions,
            "swa_recycled_pages": sched.swa_recycled_pages,
            "ttft_p50_s": ttft[len(ttft) // 2] if ttft else 0.0,
            "ttft_queue_p50_s": queue[len(queue) // 2] if queue else 0.0}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=base.list_architectures())
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model to this many layers (whole periods "
                         "of its block unit, at most the published depth); "
                         "every width stays as published")
    ap.add_argument("--engine", choices=("paged", "lockstep"),
                    default="paged")
    ap.add_argument("--batch", type=int, default=4,
                    help="lockstep wave width / paged max_seqs")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--prompt-lens", type=str, default=None,
                    help="comma-separated per-request prompt lengths "
                         "(mixed-length stream); overrides --prompt-len")
    ap.add_argument("--requests", type=int, default=None,
                    help="number of requests (default: one batch)")
    ap.add_argument("--decode-tokens", type=int, default=16)
    ap.add_argument("--sample", choices=("greedy", "temp"), default="greedy")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--kv-bits", type=int, choices=(32, 8, 4), default=None,
                    help="KV-page storage width: 32 = full precision, 8/4 "
                         "= quantized code pools (default: "
                         "REPRO_SERVE_KV_BITS or 32)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="prepend the SAME n synthetic tokens to every "
                         "prompt (a shared system prompt) — pair with "
                         "--share-prefix to map it once physically")
    ap.add_argument("--share-prefix", action="store_true",
                    help="copy-on-write prefix page sharing: requests whose "
                         "prompts share full pages with live sequences map "
                         "those physical pages instead of allocating "
                         "(attention-only archs; auto-disabled elsewhere)")
    ap.add_argument("--preempt", action="store_true",
                    help="watermark admission + priority preemption instead "
                         "of FIFO full reservation")
    ap.add_argument("--preempt-mode", choices=("recompute", "swap"),
                    default="recompute",
                    help="evicted-request readmission: recompute the prefix "
                         "or restore an NPZ swap of the slot slice")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="physical pool size (default: 2x worst-case; set "
                         "lower to exercise sharing/preemption under "
                         "pool pressure)")
    ap.add_argument("--swa-recycle", action="store_true",
                    help="sliding-window archs: recycle pages that fall "
                         "fully outside the attention window mid-request")
    ap.add_argument("--repeat", type=int, default=1,
                    help="duplicate the prompt list this many times — "
                         "repeated identical prompts make the stream "
                         "prefix-heavy (with --share-prefix the duplicates "
                         "admit as full-prompt page hits and fork on write)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of this run "
                         "to PATH (same as REPRO_TRACE=PATH; strictly "
                         "host-side — compiled programs and token streams "
                         "are unchanged, see DESIGN.md §Observability)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    compile_cache.enable()
    if args.trace:
        obs_trace.enable(args.trace)
    cfg = base.cli_config(args.arch, smoke=args.smoke, layers=args.layers,
                          tag="serve")
    if cfg.is_encoder_decoder and args.engine == "paged":
        # encoder-decoder cross caches are not paged (DESIGN.md §Serving)
        print(f"[serve] {cfg.name} is encoder-decoder: falling back to "
              f"--engine lockstep")
        args.engine = "lockstep"
    if args.prompt_lens:
        prompt_lens = [int(x) for x in args.prompt_lens.split(",")]
    else:
        prompt_lens = [args.prompt_len] * (args.requests or args.batch)
    print(f"[serve] arch={cfg.name} engine={args.engine} "
          f"requests={len(prompt_lens)} prompt_lens={prompt_lens} "
          f"decode={args.decode_tokens} sample={args.sample}")

    params = registry.init_params(cfg, jax.random.PRNGKey(args.seed))
    prompts = make_prompts(cfg, prompt_lens, args.seed,
                           prefix_len=args.prefix_len)
    # identical copies, not same-length fresh prompts: the duplicates are
    # byte-equal token streams, so with --share-prefix they admit as
    # full-prompt prefix hits and copy-on-write fork at first decode
    prompts = prompts * max(1, args.repeat)
    prompt_lens = [len(p) for p in prompts]

    if args.engine == "lockstep":
        out = run_lockstep(cfg, params, prompts, args.decode_tokens,
                           sample=args.sample, temperature=args.temperature,
                           batch=args.batch, seed=args.seed)
    else:
        kinds = Scheduler._block_kinds(cfg)
        if args.share_prefix and not kinds <= set(paging._ATTN_KINDS):
            # recurrent state is not paged, so there is nothing to share —
            # mirror the encoder-decoder fallback rather than erroring out
            print(f"[serve] {cfg.name} has non-attention blocks "
                  f"({sorted(kinds - set(paging._ATTN_KINDS))}): disabling "
                  f"--share-prefix")
            args.share_prefix = False
        if args.swa_recycle and (
                kinds != {"swa"}
                or getattr(cfg, "sliding_window", None) is None):
            print(f"[serve] {cfg.name} is not pure sliding-window "
                  f"attention: disabling --swa-recycle")
            args.swa_recycle = False
        max_ctx = max(prompt_lens) + args.decode_tokens
        pages_per_seq = paging.pages_needed(max_ctx, args.page_size)
        scfg = ServeConfig(
            max_seqs=args.batch, page_size=args.page_size,
            num_pages=args.num_pages or args.batch * pages_per_seq * 2,
            pages_per_seq=pages_per_seq,
            prefill_chunk=args.prefill_chunk, sample=args.sample,
            temperature=args.temperature, seed=args.seed,
            share_prefix=args.share_prefix, preempt=args.preempt,
            preempt_mode=args.preempt_mode, swa_recycle=args.swa_recycle,
            **({} if args.kv_bits is None else {"kv_bits": args.kv_bits}))
        out = run_paged(cfg, params, prompts, args.decode_tokens,
                        serve_cfg=scfg)
    print(f"[serve] {len(prompt_lens)}x{args.decode_tokens} tokens in "
          f"{out['wall_s']:.2f}s ({out['tokens_per_s']:.1f} tok/s "
          f"aggregate, {out['decode_steps']} decode steps)")
    if args.engine == "paged":
        print(f"[serve] pages: alloc_events={out['pages_alloc_events']} "
              f"shared_hits={out['shared_page_hits']} "
              f"cow_forks={out['cow_forks']} "
              f"preemptions={out['preemptions']} "
              f"swa_recycled={out['swa_recycled_pages']} "
              f"peak_in_use={out['peak_pages_in_use']}")
        print(f"[serve] ttft p50={out['ttft_p50_s'] * 1e3:.1f}ms "
              f"(queue {out['ttft_queue_p50_s'] * 1e3:.1f}ms)")
    print(f"[serve] sample continuation (req 0): "
          f"{out['outputs'][0].tolist()}")
    if args.trace:
        obs_trace.save()
    return out


if __name__ == "__main__":
    main()
