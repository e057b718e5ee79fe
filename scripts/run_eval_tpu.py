"""End-to-end evaluation on the real TPU chip — the honest artifact run.

Reference flow being reproduced (tools/evaluation/rag_evaluator/
evaluator.py:95-232 + results/qna.json): a served model behind the
chain server, a distinct-question dataset, RAGAS + LLM-judge metrics,
one committed JSON report.

Topology (the reference's deployment shape, all real code paths):

  [A] serving server  — seeded tiny HF checkpoint from disk through
      models/hf_loader onto the TPU chip; /v1 OpenAI endpoints
  [B] chain server    — developer_rag pipeline, llm.model_engine=openai
      pointed at [A]; hash embedder (labeled in the report)
  [C] eval CLI        — uploads the docs corpus to [B], answers the
      distinct questions in eval_results/qa_dataset.json over HTTP,
      grades with the SAME served model via [A]

Environment limitation (recorded inside the report): released weights
are not downloadable here, so the checkpoint is seeded — generation and
judge quality are those of a random-weight model. The run therefore
measures that the full serving/retrieval/eval machinery works end to
end on hardware, NOT model quality. With real weights on a TPU VM the
same command line produces a quality measurement.

Run from the repo root: python scripts/run_eval_tpu.py
The report lands in eval_results/eval_report.json and is not committed.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DOCS = ["docs/architecture.md", "docs/deployment.md",
        "docs/observability.md", "docs/support-matrix.md"]
SERVE_PORT, CHAIN_PORT = 8199, 8198


def wait_http(url: str, timeout_s: float) -> None:
    t0 = time.time()
    while time.time() - t0 < timeout_s:
        try:
            with urllib.request.urlopen(url, timeout=2):
                return
        except Exception:
            time.sleep(1.0)
    raise TimeoutError(f"{url} not up after {timeout_s}s")


def main() -> int:
    from tests.test_checkpoint_e2e import write_tiny_hf_checkpoint

    procs = []
    td = tempfile.mkdtemp(prefix="eval_tpu_")
    try:
        ckpt = os.path.join(td, "tiny-llama")
        write_tiny_hf_checkpoint(ckpt)
        print(f"[eval-tpu] seeded HF checkpoint at {ckpt}")

        env_a = dict(os.environ,
                     APP_ENGINE_WEIGHTSPATH=ckpt,
                     APP_LLM_MODELNAME="tiny-llama-seeded",
                     # Byte tokenizer: ~1 token per character, so RAG
                     # and judge prompts (context + answer + template)
                     # run 3-5k tokens — 8k context with a 4k direct-
                     # prefill bucket keeps them off the chunked path.
                     APP_ENGINE_MAXBATCHSIZE="4",
                     APP_ENGINE_MAXSEQLEN="16384",
                     APP_ENGINE_PAGESIZE="128",
                     APP_ENGINE_PREFILLBUCKETS="[512, 4096]")
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "generativeaiexamples_tpu.serving",
             "--port", str(SERVE_PORT)],
            cwd=ROOT, env=env_a,
            stderr=open(os.path.join(td, "serving.log"), "w")))
        wait_http(f"http://127.0.0.1:{SERVE_PORT}/health", 900)
        print("[eval-tpu] serving server up (TPU engine)")

        env_b = dict(os.environ,
                     APP_LLM_MODELENGINE="openai",
                     APP_LLM_SERVERURL=f"http://127.0.0.1:{SERVE_PORT}/v1",
                     APP_LLM_MODELNAME="tiny-llama-seeded",
                     APP_EMBEDDINGS_MODELENGINE="lexical",
                     # One process per chip: the engine server above
                     # holds it, the chain server stays on the CPU.
                     JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "generativeaiexamples_tpu.api.server",
             "--port", str(CHAIN_PORT)],
            cwd=ROOT, env=env_b,
            stderr=open(os.path.join(td, "chain.log"), "w")))
        wait_http(f"http://127.0.0.1:{CHAIN_PORT}/health", 120)
        print("[eval-tpu] chain server up")

        out = os.path.join(ROOT, "eval_results", "eval_report.json")
        cli = subprocess.run(
            [sys.executable, "-m", "generativeaiexamples_tpu.eval",
             "--docs", *DOCS,
             "--qa-file", "eval_results/qa_dataset.json",
             "--server", f"http://127.0.0.1:{CHAIN_PORT}",
             "--out", out,
             "--note", "SEEDED-WEIGHTS RUN: checkpoint is a seeded tiny "
                       "llama (no pretrained weights downloadable in this "
                       "environment). Scores measure that serving + "
                       "retrieval + eval plumbing work end to end on the "
                       "TPU chip, NOT model quality.",
             "--note", "generation: chain server -> OpenAI connector -> "
                       "serving engine (hf_loader checkpoint) on one real "
                       "TPU v5e chip",
             "--note", "grader/judge: the same served tiny model; judge "
                       "JSON parse failures count as unrated (None)",
             "--note", "retrieval embedder: LexicalEmbedder (hashed "
                       "TF-IDF, model-free) — real lexical retrieval; "
                       "dense BERT weights face the same download "
                       "limitation",
             "--note", "the ragas context_*/faithfulness/answer_* "
                       "metrics are LLM-GRADED: with the seeded random-"
                       "weight judge they read 0/null by construction "
                       "and say nothing about retrieval. Retrieval "
                       "quality is measured WITHOUT an LLM in the "
                       "'retrieval' section (hit@k / MRR vs each "
                       "question's ground_truth_context)."],
            cwd=ROOT, env=env_b)
        print(f"[eval-tpu] eval CLI rc={cli.returncode}; report at {out}")
        if cli.returncode == 0:
            with open(out) as fh:
                rep = json.load(fh)
            qs = [r["question"] for r in rep.get("rows", [])]
            assert len(set(qs)) == len(qs) and len(qs) >= 20, \
                "expected >= 20 distinct questions"
            assert rep["retrieval"]["n_scored"] >= 20, rep["retrieval"]
            print(json.dumps({"ragas": rep["ragas"],
                              "retrieval": rep["retrieval"],
                              "judge": rep["llm_judge"].get("mean_rating"),
                              "distinct_questions": len(set(qs))}, indent=2))
        return cli.returncode
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        for name in ("serving.log", "chain.log"):
            path = os.path.join(td, name)
            if os.path.isfile(path):
                with open(path) as fh:
                    tail = fh.read()[-800:]
                if tail:
                    print(f"[eval-tpu] {name} tail:\n{tail}")


if __name__ == "__main__":
    raise SystemExit(main())
