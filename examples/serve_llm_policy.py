"""Central-inference serving at LM scale (SEED's design applied to an LLM
policy): batched prefill + decode behind the InferenceServer, with
straggler-deadline batching — the serve_step the decode_32k dry-run lowers,
runnable here on a reduced config.

    PYTHONPATH=src python examples/serve_llm_policy.py --arch gemma2-9b
"""

import argparse
import queue
import sys
import threading
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import make_model, smoke_config
from repro.core.inference import InferenceServer
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import make_prefill, make_serve_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-9b")
    ap.add_argument("--clients", type=int, default=6)
    ap.add_argument("--tokens", type=int, default=12)
    args = ap.parse_args()

    cfg = smoke_config(args.arch)
    bundle = make_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    max_len = 64

    prefill = jax.jit(make_prefill(bundle, max_len=max_len, dtype=jnp.float32))
    sstep = jax.jit(make_serve_step(bundle))

    # one shared cache batch: one row per client (continuous-batching-lite).
    # The server assigns dense (actor, lane) slots in first-sight order, NOT
    # by client id — rows are interchangeable here only because every client
    # shares the same zero prompt; per-client prompts would need prefill
    # keyed through server.slot_ids().
    prompt = jnp.zeros((args.clients, 8), jnp.int32)
    tok, cache = prefill(params, {"tokens": prompt})
    state = {"tok": tok, "cache": cache}

    def policy_step(obs, ids):
        # obs carries the clients' last tokens; decode one step for ALL slots
        t = state["tok"].at[jnp.asarray(ids), 0].set(jnp.asarray(obs[:, 0]))
        nxt, state["cache"] = sstep(params, t, state["cache"])
        state["tok"] = nxt
        return np.asarray(nxt)[ids, 0]

    server = InferenceServer(policy_step, max_batch=args.clients,
                             deadline_ms=3.0)
    server.start()

    results = {i: [] for i in range(args.clients)}

    def client(cid):
        tok = cid + 1
        for _ in range(args.tokens):
            if cid == 0:
                time.sleep(0.004)        # a deliberate straggler
            reply = server.submit(cid, np.array([[tok]], np.int32)[0])
            tok = int(reply.get(timeout=10.0))
            results[cid].append(tok)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    server.stop()

    total = args.clients * args.tokens
    print(f"== {args.arch} (reduced): {total} tokens for {args.clients} "
          f"clients in {dt:.2f}s ({total/dt:.0f} tok/s)")
    print(f"   batches={server.stats['batches']} "
          f"occupancy={server.stats['batch_occupancy']/max(server.stats['batches'],1):.2f} "
          f"(straggler deadline kept batches moving)")
    for cid, toks in results.items():
        print(f"   client {cid}: {toks[:8]}...")
    print("ok")


if __name__ == "__main__":
    main()
