"""Serving launcher: RAG pipeline (retrieval + generation) for any arch.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --num-docs 256 --requests 8 [--metric cosine] [--topk 3]

Without --smoke the generator and the minilm-embedder run at their FULL
widths from `repro.configs`. Builds the offline index (MiniLM-style
embedder -> INT8 nibble-planar DB, sharded over the mesh when
--data/--model > 1), then serves batched
requests through the paper's two-stage hierarchical retrieval and the
generator's prefill+decode, logging the Table-II-calibrated energy ledger
per query.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core import RetrievalConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import embedder, get_model
from repro.serve import RAGPipeline


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced generator/embedder configs (CI, CPU)")
    ap.add_argument("--num-docs", type=int, default=256)
    ap.add_argument("--doc-len", type=int, default=12)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--topk", type=int, default=3)
    ap.add_argument("--metric", choices=("cosine", "mips"), default="cosine")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    args = ap.parse_args(argv)

    enable_compile_cache()
    rng = np.random.default_rng(0)
    gcfg = get_config(args.arch, smoke=args.smoke)
    if gcfg.family == "encdec":
        raise SystemExit("RAG serving drives decoder-LM archs; "
                         "seamless decodes from frames, not augmented text")
    gen_api = get_model(gcfg)
    gen_params = gen_api.init(jax.random.PRNGKey(0))

    ecfg = get_config("minilm-embedder", smoke=args.smoke)
    eparams = embedder.init_params(ecfg, jax.random.PRNGKey(1))

    vocab = min(gcfg.vocab_size, ecfg.vocab_size)   # docs feed both models
    docs = jnp.asarray(rng.integers(
        0, vocab, (args.num_docs, args.doc_len)).astype(np.int32))
    mesh = (make_test_mesh(args.data, args.model)
            if args.data * args.model > 1 else None)
    t0 = time.time()
    pipe = RAGPipeline.build(
        ecfg, eparams, gen_api, gen_params, docs,
        RetrievalConfig(k=args.topk, metric=args.metric), mesh=mesh)
    print(f"[offline] index over {args.num_docs} docs in "
          f"{time.time() - t0:.1f}s (mesh={'none' if mesh is None else dict(mesh.shape)})")

    gold = rng.integers(0, args.num_docs, args.requests)
    queries = docs[jnp.asarray(gold)]
    t0 = time.time()
    out, ids, ledger = pipe.answer(queries, max_new=args.max_new)
    dt = time.time() - t0
    hits = int(np.sum(np.asarray(ids)[:, 0] == gold))
    print(f"[online] {args.requests} reqs in {dt:.1f}s; top-1 hit "
          f"{hits}/{args.requests}; retrieval energy "
          f"{ledger.total_uj:.2f} uJ/query "
          f"(DRAM {100 * ledger.proportions()['DRAM']:.1f}%)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
