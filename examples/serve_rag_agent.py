"""End-to-end driver (the paper's kind: SERVING): a RAG-enabled agent
answering batched requests.

Pipeline (paper Fig. 1): personal-record corpus -> MiniLM-style embedder
-> INT8 nibble-planar database -> per request batch: encode query ->
TWO-STAGE HIERARCHICAL RETRIEVAL -> augmented prompt -> batched
prefill+decode on the generator LM. Logs the paper's per-query retrieval
energy ledger alongside the generations.

    PYTHONPATH=src python examples/serve_rag_agent.py [--requests 8] [--smoke]

Without --smoke the generator (qwen2-0.5b) and the embedder
(minilm-embedder) run at their FULL widths from `repro.configs`.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import RetrievalConfig
from repro.models import embedder, get_model
from repro.serve import RAGPipeline


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--num-docs", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced generator/embedder configs (CPU)")
    args = ap.parse_args()
    rng = np.random.default_rng(0)

    # generator: qwen2-family LM served greedily
    gcfg = get_config("qwen2-0.5b", smoke=args.smoke)
    gen_api = get_model(gcfg)
    gen_params = gen_api.init(jax.random.PRNGKey(0))

    # embedder: MiniLM-style sentence encoder (the paper's)
    ecfg = get_config("minilm-embedder", smoke=args.smoke)
    eparams = embedder.init_params(ecfg, jax.random.PRNGKey(1))

    # offline phase: the "personal medical record" corpus (synthetic
    # tokens valid for both models' vocabularies)
    vocab = min(gcfg.vocab_size, ecfg.vocab_size)
    doc_tokens = jnp.asarray(
        rng.integers(0, vocab, (args.num_docs, 12)).astype(np.int32))
    t0 = time.time()
    pipe = RAGPipeline.build(ecfg, eparams, gen_api, gen_params, doc_tokens,
                             RetrievalConfig(k=2, metric="cosine"))
    print("[offline] built INT8 nibble-planar index over "
          f"{args.num_docs} docs in {time.time()-t0:.1f}s")

    # online phase: batched requests (queries = noisy copies of docs so the
    # retrieval ground truth is visible in the log)
    gold = rng.integers(0, args.num_docs, args.requests)
    queries = doc_tokens[jnp.asarray(gold)]
    t0 = time.time()
    out, ids, ledger = pipe.answer(queries, max_new=args.max_new)
    dt = time.time() - t0
    hits = int(np.sum(np.asarray(ids)[:, 0] == gold))
    print(f"[online] {args.requests} requests in {dt:.1f}s "
          f"({dt/args.requests:.2f}s/req incl. retrieval + "
          f"{args.max_new}-token decode)")
    print(f"  retrieval top-1 hit rate: {hits}/{args.requests}")
    print("  retrieval energy (paper cost model): "
          f"{ledger.total_uj:.2f} uJ/query, "
          f"DRAM share {100*ledger.proportions()['DRAM']:.1f}%")
    for i in range(min(3, args.requests)):
        print(f"  req{i}: retrieved docs {np.asarray(ids)[i].tolist()} "
              f"(gold {gold[i]}) -> tokens {np.asarray(out)[i][:8].tolist()}…")


if __name__ == "__main__":
    main()
