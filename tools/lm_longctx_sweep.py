"""Long-context sweep: TransformerLM step time + compiled HBM vs length.

Runs the production train step (make_train_step — forward, backward,
adam, step increment in ONE executable) across sequence lengths and
attention/remat variants on whatever chip is default, and prints one
JSON line per config:

  {"seq_len": N, "variant": "...", "ms_per_step": ..., "tokens_per_sec":
   ..., "temp_bytes": ..., "arg_bytes": ..., "status": "ok"|"oom"}

``temp_bytes`` is the XLA compiler's own peak-temporary-allocation
figure (``compiled.memory_analysis()``) — exact and reproducible, where
the runtime's ``memory_stats()`` peak depends on what else the process
holds. OOMs (compile- or run-time) are caught and recorded,
not crashed on: hitting the dense wall IS a datapoint.

Usage: python tools/lm_longctx_sweep.py [--quick]
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp


def run_config(seq_len: int, variant: str, batch: int = 8,
               d_model: int = 256, num_heads: int = 4,
               num_blocks: int = 4, steps: int = 10,
               vocab_size: int = 64, attn_block_size: int = 512) -> dict:
    """``variant`` tokens: "dense"/"block" (attention form), "+remat",
    "+ce" (streamed loss head, ce_block=attn_block_size — the
    vocab-axis flash; without it the head materializes (B, S, V) f32
    logits + grads)."""
    from distributed_tensorflow_tpu.data.lm import LMDataSet
    from distributed_tensorflow_tpu.models.transformer import TransformerLM
    from distributed_tensorflow_tpu.training import (
        create_train_state,
        get_optimizer,
        make_train_step,
    )

    attn_block = attn_block_size if "block" in variant else None
    remat = "remat" in variant
    ce_block = attn_block_size if "ce" in variant else None
    rec = {"seq_len": seq_len, "variant": variant, "batch": batch,
           "d_model": d_model, "num_blocks": num_blocks,
           "vocab_size": vocab_size}
    model = TransformerLM(vocab_size=vocab_size, seq_len=seq_len,
                          d_model=d_model, num_heads=num_heads,
                          num_blocks=num_blocks,
                          attn_block=attn_block, remat=remat,
                          ce_block=ce_block,
                          compute_dtype=jnp.bfloat16)
    opt = get_optimizer("adam", 1e-3)
    step = make_train_step(model, opt, keep_prob=1.0)
    try:
        state = create_train_state(model, opt, seed=0)
        ds = LMDataSet(max(batch, 8), seq_len=seq_len,
                       vocab_size=vocab_size, seed=0)
        b = ds.next_batch(batch)
        lowered = step.lower(state, b)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        if ma is not None:
            rec["temp_bytes"] = int(ma.temp_size_in_bytes)
            rec["arg_bytes"] = int(ma.argument_size_in_bytes)
        state, m = compiled(state, b)
        jax.block_until_ready(state.params)
        t0 = time.time()
        for _ in range(steps):
            state, m = compiled(state, b)
        jax.block_until_ready(state.params)
        dt = (time.time() - t0) / steps
        rec["ms_per_step"] = round(dt * 1000, 2)
        rec["tokens_per_sec"] = round(batch * seq_len / dt)
        rec["loss"] = round(float(m["loss"]), 4)
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — OOM is a datapoint
        msg = str(e)
        if ("RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
                or "exceeds" in msg):
            rec["status"] = "oom"
        else:
            rec["status"] = "error"
        rec["error"] = msg[:200]
    return rec


def main():
    quick = "--quick" in sys.argv
    vocab = "--vocab" in sys.argv
    if vocab:
        # the vocab axis (r5): at real vocab sizes the UNSTREAMED loss
        # head's (B, S, V) f32 logits+grads dwarf what the flash
        # attention backward saved; "+ce" streams them (ce_block).
        # Expect: naive head OOMs/compile-fails where block+ce runs.
        for v_size in (8192, 32768):
            for s in (4096, 8192, 16384):
                for var in ("block", "block+ce"):
                    # the naive head hitting its wall IS a datapoint —
                    # no skip for the "block" (unstreamed-loss) rows
                    print(json.dumps(run_config(s, var, vocab_size=v_size)),
                          flush=True)
        return
    lengths = [512, 2048, 4096] if quick else [512, 1024, 2048, 4096, 8192,
                                               16384]
    variants = ["dense", "dense+remat", "block", "block+remat"]
    for s in lengths:
        for v in variants:
            if s > 8192 and "block" not in v:
                continue  # dense past 8k: known wall, skip the compile
            print(json.dumps(run_config(s, v)), flush=True)


if __name__ == "__main__":
    main()
