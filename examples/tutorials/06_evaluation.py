# %% [markdown]
# # 06 — Evaluating a RAG pipeline
#
# The reference treats evaluation as its test suite (SURVEY.md §4):
# synthesize QA pairs from the corpus, answer them through the
# pipeline, score with RAGAS-style metrics plus an LLM judge
# (`tools/evaluation/` notebooks 01-04). This tutorial walks the same
# four stages with `eval/` — hermetic (scripted LLM), CI-runnable.
# The one-command version is:
#
#     python -m generativeaiexamples_tpu.eval --docs README.md --offline
#
# and `scripts/run_eval_e2e.py` runs it against a REAL chain server +
# engine, writing `eval_results/eval_report.json` (not committed).

# %%
import json
import os
import sys

_here = (os.path.dirname(os.path.abspath(__file__))
         if "__file__" in globals() else os.getcwd())
ROOT = os.path.abspath(os.path.join(_here, "..", ".."))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# %% [markdown]
# ## Stage 1 — synthetic QA generation
# An LLM reads each corpus chunk and writes a question/answer pair
# (the reference's `synthetic_data_generator/data_generator.py`).

# %%
from generativeaiexamples_tpu.config.wizard import load_config
from generativeaiexamples_tpu.connectors.fakes import EchoLLM, HashEmbedder
from generativeaiexamples_tpu.eval import harness
from generativeaiexamples_tpu.rag.documents import load_document
from generativeaiexamples_tpu.rag.splitter import get_text_splitter

cfg = load_config(path="", env={})
splitter = get_text_splitter(cfg)
chunks = []
readme = os.path.join(ROOT, "README.md")
for d in load_document(readme, "README.md"):
    chunks.extend(splitter.split(d.text))
print(f"corpus: {len(chunks)} chunks")

qa_llm = EchoLLM(script=[(
    "question-answer pair",
    json.dumps({"question": "What serves the LLM in this framework?",
                "answer": "An in-process TPU serving engine."}))])
qa_rows = harness.generate_synthetic_qa(qa_llm, chunks, n_pairs=4)
print(f"stage 1: {len(qa_rows)} QA pairs; first:",
      qa_rows[0]["question"])
assert qa_rows and "ground_truth_answer" in qa_rows[0]

# %% [markdown]
# ## Stage 2 — answer generation through the pipeline
# Online mode posts each question to a chain server
# (`harness.ChainServerClient` + `generate_answers`); here we run the
# pipeline in-process, which is what `--offline` does.

# %%
from generativeaiexamples_tpu.pipelines.base import get_example_class
from generativeaiexamples_tpu.pipelines.resources import Resources

answer_llm = EchoLLM(prefix="The engine answers: ")
res = Resources(cfg, llm=answer_llm, embedder=HashEmbedder(64),
                reranker=None)
rag = get_example_class("developer_rag")(res)
rag.ingest_docs(readme, "README.md")

rows = []
for qa in qa_rows:
    ctx = [h["content"] for h in rag.document_search(qa["question"], 4)]
    answer = "".join(rag.rag_chain(qa["question"], [], max_tokens=128))
    rows.append({**qa, "generated_answer": answer,
                 "retrieved_context": ctx})
print("stage 2 row keys:", sorted(rows[0]))
assert all(r["generated_answer"] for r in rows)

# %% [markdown]
# ## Stage 3 — RAGAS-style metrics
# Six metrics (faithfulness, answer/context relevancy, context
# precision/recall, answer similarity) plus the harmonic-mean
# `ragas_score` over the core four — the reference's
# `evaluator.py:92-158` contract. Metric probes are yes/no LLM calls;
# the scripted judge answers yes.

# %%
from generativeaiexamples_tpu.eval.metrics import RagasEvaluator

metric_llm = EchoLLM(script=[("Answer yes or no", "yes")])
ragas = RagasEvaluator(metric_llm, HashEmbedder(64)).evaluate(rows)
print(json.dumps({k: (round(v, 3) if isinstance(v, float) else v)
                  for k, v in ragas.items()}, indent=1))
assert ragas["ragas_score"] is not None

# %% [markdown]
# ## Stage 4 — LLM judge (Likert 1-5, few-shot)
# The judge grades each generated answer against the ground truth with
# a rating + explanation (`evaluator.py:160-232` parity).

# %%
from generativeaiexamples_tpu.eval.metrics import eval_llm_judge

judge_llm = EchoLLM(script=[
    ("You are grading answers",
     '{"rating": 4, "explanation": "grounded in the retrieved context"}')])
judge = eval_llm_judge(judge_llm, rows)
print("judge mean:", judge["mean_rating"], "n:", len(judge["details"]))
assert judge["mean_rating"] == 4.0

# %% [markdown]
# ## The combined report
# `harness.run_eval` packages stages 3+4; `save_report` writes the same
# JSON shape the reference checks in under
# `tools/evaluation/results/qna.json` — see `eval_results/` in this
# repo for a committed run against the real engine.

# %%
report = harness.run_eval(metric_llm, HashEmbedder(64), rows,
                          judge_llm=judge_llm)
print("ragas_score:", report["ragas"]["ragas_score"],
      "| judge:", report["llm_judge"]["mean_rating"])
assert report["n"] == len(rows)
