"""A benchmark root of toy cells in a temporary directory.

It adds configurations, one traffic mix of each kind, cells, per-layer
metrics and, for the open loop that no cell of the repository's runs yet,
its end-to-end metrics as NEW files and entries beside a link to the
repository's own `perfbench/` — nothing there is edited — which is how a
later PR adds them, and what the tests drive on the CPU.
"""
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_ENGINE = {"slots": 8, "page_size": 16, "num_pages": 40,
               "chunk_buckets": [8, 16, 32], "prefix_cache": True,
               "decode_kernel": False, "async_decode": True,
               "weights_dtype": "bfloat16"}
#: the toy cell (or cells) that stand for each of the repository's
RENAME = {"train-gpt2m-1chip": ["tiny-train", "tiny-train4"],
          "serve-gpt2xl-decode-heavy": ["tiny-closed"]}
HOST_LOOP = "engine host loop (serve/engine.py)"
#: the per-layer metrics every serving cell joins, and the six that read
#: the whole window from the span log (PR 37), by name: a cell's test
#: finds its lists by these, never by how many or where they stand
GENERIC = {"slot_occupancy_pct", "host_blocked_ms_p50", "decode_step_ms_p50",
           "decode_device_ms_p50", "prefill_rows_per_call",
           "prefill_tick_share_pct", "engine_host_work_ms_p50",
           "engine_dispatch_ms_p50", "prefill_stall_share_pct",
           "host_caused_idle_pct", "setup_trace_lower_s",
           "setup_compile_or_load_s"}
WINDOW_SIX = {"queue_wait_ms_p50", "admit_to_first_token_ms_p50",
              "admission_blocked_on_pages_pct", "pages_reserved_unfilled_pct",
              "prefill_stall_window_share_pct", "engine_stall_ms_per_window"}
SCHEDULER = "scheduler and slots (serve/scheduler.py, serve/slots.py)"


def _dump(obj, *path):
    os.makedirs(os.path.dirname(os.path.join(*path)), exist_ok=True)
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f, indent=1)


def _load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def make_root(tmp: str) -> str:
    """Write the toy benchmark under `tmp` and return its root."""
    root = os.path.join(str(tmp), "root")
    os.makedirs(root)
    os.symlink(os.path.join(REPO, "perfbench"),
               os.path.join(root, "perfbench"))
    for name, positions in (("gpt2-tiny", 64), ("gpt2-tiny-serve", 128)):
        _dump({"name": name, "source": "none: a toy for the tests",
               "n_layer": 2, "n_head": 4, "n_embd": 64,
               "n_positions": positions, "vocab_size": 250,
               "assumed": {"padded_vocab_size": 256}},
              root, "extra", "configs", name + ".json")
    tdir = os.path.join(REPO, "perfbench", "traffic")
    train = _load(tdir, "pretrain-seq1024.json")
    train.update(rows_per_chip=4, seq_len=64, trace_start_s=0.1,
                 trace_seconds=0.3,
                 limits={"loss_gap": 1e-3,
                         "first_grad_norm_gap_worst_leaf": 0.05,
                         "first_grad_projection_gap_worst_leaf": 0.05,
                         "param_change_norm_gap_worst_leaf": 0.05})
    _dump(train, root, "extra", "traffic", "tiny-train.json")
    _dump(train, root, "extra", "traffic", "tiny-train-dp4.json")
    limits = {"served_logit_gap_widest": 0.01,
              "served_logprob_gap_widest": 0.006}
    closed = _load(tdir, "reason-closed.json")
    closed.update(
        engine=TINY_ENGINE, clients=8, backlog=600,
        prompt={"dist": "lognormal", "median": 16, "sigma": 0.4, "min": 8,
                "max": 32},
        output={"dist": "lognormal", "median": 24, "sigma": 0.3, "min": 12,
                "max": 48},
        max_total=128, first_wave_min_output=4, trace_start_s=0.1,
        trace_seconds=0.3, limits=limits)
    _dump(closed, root, "extra", "traffic", "tiny-closed.json")
    opened = _load(tdir, "chat-open.json")
    opened.update(
        engine=TINY_ENGINE,
        prompt={"dist": "lognormal", "median": 16, "sigma": 0.8, "min": 4,
                "max": 64},
        output={"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 2,
                "max": 32},
        max_total=128, arrivals={"process": "poisson", "rate_per_s": 20.0},
        ramp_s=0.5, tail_s=0.5, drain_limit_s=20, trace_start_s=0.1,
        trace_seconds=0.3, limits=limits)
    _dump(opened, root, "extra", "traffic", "tiny-open.json")
    # per-layer metrics of the toy's own, by an existing reader
    _dump({"layer": HOST_LOOP, "moves": "serve_tokens_per_s",
           "source": "program_span", "reader": "sample_percentile",
           "samples": "serve.tick_ms", "q": 90},
          root, "extra", "layer_metrics", "tiny_tick_ms_p90.json")
    _dump({"layer": SCHEDULER, "moves": "ttft_p90_ms",
           "source": "program_span", "reader": "sample_percentile",
           "samples": "serve.queue_wait_ms", "q": 50},
          root, "extra", "layer_metrics", "tiny_queue_wait_ms_p50.json")

    bench = _load(REPO, "BENCHMARK.json")
    bench["paths"] = ["perfbench", "extra"]
    bench["configs"] = [
        {"name": n, "source": "none", "file": f"extra/configs/{n}.json",
         "reduced": [], "why": "toy"}
        for n in ("gpt2-tiny", "gpt2-tiny-serve")]
    bench["workloads"] = [
        {"name": "tiny-train", "config": "gpt2-tiny",
         "traffic": "tiny-train", "chips": 1, "why": "toy"},
        {"name": "tiny-train4", "config": "gpt2-tiny",
         "traffic": "tiny-train-dp4", "chips": 4, "why": "toy"},
        {"name": "tiny-closed", "config": "gpt2-tiny-serve",
         "traffic": "tiny-closed", "chips": 1, "why": "toy"},
        {"name": "tiny-open", "config": "gpt2-tiny-serve",
         "traffic": "tiny-open", "chips": 1, "why": "toy"}]
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            if "workloads" in m:
                m["workloads"] = [toy for w in m["workloads"]
                                  for toy in RENAME.get(w, [])]
    bench["end_to_end"] += [
        {"name": name, "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["tiny-open"]}
        for name in ("ttft_p90_ms", "tpot_p90_ms")]
    bench["per_layer"] += [
        {"name": "tiny_tick_ms_p90", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": HOST_LOOP,
         "moves": "serve_tokens_per_s", "workloads": ["tiny-closed"]},
        {"name": "tiny_queue_wait_ms_p50", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": SCHEDULER,
         "moves": "ttft_p90_ms", "workloads": ["tiny-open"]}]
    _dump(bench, root, "BENCHMARK.json")
    return root
