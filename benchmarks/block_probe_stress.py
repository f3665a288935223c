"""A block model's golden probe served again and again beside a churning load.

    chiprun --chips 1 --timeout 1500 -- python benchmarks/block_probe_stress.py . 300
    JAX_PLATFORMS=cpu python benchmarks/block_probe_stress.py . 20 small

``LLMEngine`` as a library at ``sdar-30b-a3b-chat-bf16``'s cut (``small``:
``debug-block-moe`` on the CPU, each probe held to the first one served):
the configuration's first golden probe is sent again as soon as it ends,
beside 63 requests of 1024-1920 tokens that end after 48-160, so that an
admission falls in most programs and the probe's passes run in windows and
in mixed programs of every chunk rung. Every probe is held to the golden as
``perfbench.correctness.compare`` holds it; one that parts from it is
printed with its passes (which program ran each, the row's source, the
transferred ids and every position's log-probability a pass). What it
found (PERF.md section 6, PR 53): a probe chosen against one tree's
programs can cross a near tie in another's mixed programs; screen a new
golden's probes with this before trusting them. The first argument is the
tree to import the package from."""
import json
import os
import sys
import time


def main() -> None:
    import numpy as np
    tree, seconds = sys.argv[1], float(sys.argv[2])
    small = sys.argv[3:] == ["small"]
    sys.path.insert(0, os.path.abspath(tree))
    import kubernetes_gpu_cluster_tpu.engine
    from kubernetes_gpu_cluster_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    from kubernetes_gpu_cluster_tpu.config import (CacheConfig, EngineConfig, SchedulerConfig,
                                                   apply_hf_overrides, get_model_config)
    from kubernetes_gpu_cluster_tpu.engine import LLMEngine, SamplingParams
    from kubernetes_gpu_cluster_tpu.engine import block as block_steps
    if small:
        cfg = EngineConfig(model=get_model_config("debug-block-moe"),
                           cache=CacheConfig(page_size=16, num_pages=400),
                           scheduler=SchedulerConfig(max_num_seqs=8, max_prefill_tokens=64,
                                                     decode_buckets=(1, 2, 4, 8), prefill_buckets=(16, 32, 64)))
        golden, probe_prompt = None, list(range(3, 27))
        lo, hi, olo, ohi, seats = 20, 60, 8, 30, 8
    else:
        model = apply_hf_overrides(
            get_model_config("sdar-30b-a3b-chat"),
            {"num_hidden_layers": 6}).replace(dtype="bfloat16")
        cfg = EngineConfig(model=model, cache=CacheConfig(),
                           scheduler=SchedulerConfig(max_num_seqs=64),
                           max_model_len=4096)
        with open(os.path.join(
                tree, "perfbench/configs/sdar-30b-a3b-chat-bf16.golden.json")) as f:
            golden = json.load(f)["probes"][0]
        probe_prompt = golden["prompt"]
        lo, hi, olo, ohi, seats = 1024, 1920, 48, 160, 64
    eng = LLMEngine(cfg)
    print("engine", eng.runtime_info().get("num_pages"), flush=True)
    log = {}      # probe id -> list of replay events
    orig_replay = block_steps.replay
    def replay(engine, step, fetched, carried):
        toks, lps, commit = fetched[0], fetched[1], fetched[2]
        batch = step["batch"]
        for r, seq in batch.device_seq_rows():
            if seq.request_id.startswith("probe") and seq.request_id not in step["zombies"] and not seq.is_finished:
                log.setdefault(seq.request_id, []).append(dict(
                    step=step["step"], kind=step["kind"], row=r, rows=len(batch.temperature),
                    src=int(batch.block[r, -1]), host=[int(x) for x in batch.block[r, :-1]][-9:],
                    toks=[toks[w][r] for w in range(len(toks))],
                    lps=[[round(x, 4) for x in lps[w][r]] for w in range(len(toks))],
                    commit=[commit[w][r] for w in range(len(toks))]))
        return orig_replay(engine, step, fetched, carried)
    block_steps.replay = replay
    rng = np.random.default_rng(5)
    V = cfg.model.vocab_size
    n_load = n_probe = bad = 0
    first = None
    live_load, probe_live = 0, False
    t0, warm_until = time.time(), None
    while True:
        now = time.time()
        if warm_until is None and eng.step_count > 40:
            warm_until = t0 = now
        if warm_until is not None and now - t0 > seconds:
            break
        while live_load < seats - 1:
            n = int(rng.integers(lo, hi))
            n_load, live_load = n_load + 1, live_load + 1
            eng.add_request(f"load{n_load}", rng.integers(3, V - 1, n).tolist(),
                            SamplingParams(max_tokens=int(rng.integers(olo, ohi)), temperature=0.0, ignore_eos=True))
        if not probe_live:
            n_probe, probe_live = n_probe + 1, True
            eng.add_request(f"probe{n_probe}", probe_prompt, SamplingParams(
                max_tokens=8, temperature=0.0, logprobs=True, top_logprobs=5, ignore_eos=True))
        for o in eng.step():
            if not o.finished:
                continue
            if o.request_id.startswith("load"):
                live_load -= 1
                continue
            probe_live = False
            got = (list(o.output_token_ids), [round(x, 4) for x in o.output_logprobs])
            if first is None:
                first = got
                print("first", got, flush=True)
            gap = None
            if golden is not None:
                gap, ok = 0.0, True
                for i, gid in enumerate(golden["tokens"]):
                    top = dict(o.output_top_logprobs[i])
                    if gid not in top:
                        ok = False
                        break
                    gap = max(gap, abs(top[gid] - golden["logprobs"][i]))
                    if got[0][i] != gid:
                        break
                wrong = (not ok) or gap > 0.09
            else:
                wrong = got[0] != first[0] or max(abs(a - b) for a, b in zip(got[1], first[1])) > 1e-3
            if wrong:
                bad += 1
                print("BAD", o.request_id, "gap", gap, got, json.dumps(log.get(o.request_id)), flush=True)
            elif n_probe % 25 == 0:
                print("ok", o.request_id, "gap", gap, "steps", eng.step_count, "t", round(time.time() - t0), flush=True)
            log.pop(o.request_id, None)
    print("DONE tree", tree, "probes", n_probe, "bad", bad, "loads", n_load, "steps", eng.step_count,
          "commits", eng.obs.block_commits if hasattr(eng.obs, "block_commits") else eng.obs.block_commit_passes,
          "preemptions", eng.scheduler.num_preemptions, flush=True)


if __name__ == "__main__":
    main()
