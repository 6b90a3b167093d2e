"""A checkout in a temporary directory for the CPU tests: the
benchmark's own files copied, and toy cells added to them as new files
and new entries only — which is also the proof that a later PR can add
a configuration, a mix, a reader, a family and a kind that way."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

TOY_CELLS = {
    "gpt2-toy.fit-toy": ("fit-toy", 1, ["train_tokens_per_s"]),
    "gpt2-toy.fit-toy-x4": ("fit-toy-x4", 4, ["train_tokens_per_s"]),
    "gpt2-toy.serve-offline-toy": ("serve-offline-toy", 1,
                                   ["serve_tokens_per_s"]),
    "gpt2-toy.serve-chat-toy": ("serve-chat-toy", 1,
                                ["request_p95_ms", "per_token_p95_ms"]),
}


def make_checkout(dst: str) -> str:
    """``dst`` becomes a checkout holding the real benchmark plus the toy
    configuration and cells. Nothing that was copied is edited, except
    ``BENCHMARK.json``, which only gains entries."""
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for sub in ("configs", "traffic"):
        for name in os.listdir(os.path.join(HERE, "data", sub)):
            shutil.copy(os.path.join(HERE, "data", sub, name),
                        os.path.join(dst, "benchmark", sub, name))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the chat cell's entries, which PR 24 measured and did not ship
    # (PERF.md section 7): added here as the later PR would add them, with
    # no file of the benchmark edited
    with open(os.path.join(HERE, "data", "chat_cell_entries.json")) as f:
        chat = json.load(f)
    have = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for key in ("end_to_end", "per_layer"):
        for m in chat[key]:
            if m["name"] not in have:
                bench[key].append(dict(m, workloads=[]))
    bench["configs"].append({
        "name": "gpt2-toy", "source": "none: a toy for the CPU tests",
        "file": "benchmark/configs/gpt2-toy.json", "reduced": [],
        "why": "toy"})
    for name, (mix, chips, e2e) in TOY_CELLS.items():
        bench["workloads"].append({"name": name, "config": "gpt2-toy",
                                   "traffic": mix, "chips": chips,
                                   "why": "toy"})
        for m in bench["end_to_end"]:
            if m["name"] in e2e:
                m["workloads"].append(name)
        for m in bench["per_layer"]:
            if "workloads" in m and m["moves"] in e2e:
                m["workloads"].append(name)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return dst
