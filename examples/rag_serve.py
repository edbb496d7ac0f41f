"""End-to-end driver: PiPNN as the retrieval substrate of a serving stack.

Pipeline (the paper's RAG motivation, Sec. 1, realized):
  1. build a PiPNN index over a corpus of document embeddings;
  2. serve an LM (any --arch, reduced config on CPU) with batched
     requests: each request embeds its prompt, retrieves top-k documents
     by MIPS through the PiPNN graph, prepends the retrieved doc tokens,
     then prefill+decode generates the continuation.

  PYTHONPATH=src python examples/rag_serve.py --arch qwen2-7b \
      --requests 8 --batch 4 --ann-dtype int8
"""
import argparse
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.launch.serve import RETRIEVER_DTYPES, Retriever, Server

DOC_LEN = 16


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--corpus", type=int, default=8192)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--topk", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--ann-dtype", choices=RETRIEVER_DTYPES, default="f32",
                    help="serving precision of the corpus copy; int8 = "
                         "scalar-quantized packing (~1/4 the footprint)")
    args = ap.parse_args()

    rng = np.random.default_rng(0)

    # --- 1. corpus: embeddings + token payloads --------------------------
    t0 = time.perf_counter()
    centers = rng.standard_normal((64, args.dim)) * 2.0
    assign = rng.integers(0, 64, args.corpus)
    corpus_emb = (centers[assign]
                  + 0.5 * rng.standard_normal((args.corpus, args.dim))
                  ).astype(np.float32)
    retriever = Retriever(corpus_emb, points_dtype=args.ann_dtype,
                          metric="mips", seed=0)
    print(f"[index] {args.corpus} docs indexed in "
          f"{time.perf_counter() - t0:.2f}s "
          f"(avg deg {retriever.index.average_degree():.1f}, "
          f"{args.ann_dtype} serving copy: "
          f"{retriever.device_bytes() / 1e6:.2f} MB on device)")

    # --- 2. server --------------------------------------------------------
    max_len = args.topk * DOC_LEN + args.prompt_len + args.max_new
    server = Server(args.arch, smoke=True, max_len=max_len)
    doc_tokens = rng.integers(0, server.vocab,
                              (args.corpus, DOC_LEN)).astype(np.int32)

    # prompt "embedder": project prompt token ids into corpus space (stub
    # for a real encoder; deterministic so retrieval is reproducible)
    proj = rng.standard_normal((args.prompt_len, args.dim)).astype(np.float32)

    served = 0
    t_all = time.perf_counter()
    while served < args.requests:
        b = min(args.batch, args.requests - served)
        prompts = rng.integers(0, server.vocab,
                               (b, args.prompt_len)).astype(np.int32)
        q_emb = (prompts / server.vocab) @ proj          # [b, dim]
        hits = retriever.retrieve(q_emb, k=args.topk, beam=32)
        aug = np.concatenate(
            [doc_tokens[hits.reshape(b, -1)].reshape(b, -1), prompts],
            axis=1)
        toks, stats = server.generate(aug, args.max_new)
        served += b
        print(f"[serve] batch of {b}: retrieved {args.topk} docs/req, "
              f"prefill {stats['prefill_s'] * 1e3:.0f}ms, "
              f"decode {stats['decode_tok_per_s']:.0f} tok/s")
    dt = time.perf_counter() - t_all
    print(f"[done] {served} RAG requests in {dt:.2f}s "
          f"({served / dt:.2f} req/s end-to-end)")


if __name__ == "__main__":
    main()
