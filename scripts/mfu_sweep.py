"""MFU ladder: sweep attention impl x micro-batch x remat on the real chip.

Config entries: [seq_len, micro_bs, attention_impl, remat_policy] with two
optional trailing fields [, preset [, optimizer]] — preset one of
bench.BENCH_PRESETS (default qwen3_0p6b), optimizer passed to
build_optimizer (default adamw; "muon" fits the 1p7b preset on one v5e).

Run:  python scripts/mfu_sweep.py            # full ladder
      SWEEP_CONFIGS='[[4096,8,"xla","dots"],
                      [2048,4,"xla_twopass","ctx","qwen3_1p7b","muon"]]' \
          python scripts/mfu_sweep.py

Modes:
  in-process (default): one backend init for the whole ladder — fastest,
      but an execution that never returns strands every remaining config.
  SWEEP_SUBPROCESS=1: each config runs in its own python subprocess with a
      SWEEP_CONFIG_TIMEOUT_S kill budget (default 1500s) — a hang costs one
      config, and each child pays its own backend start. The parent never
      starts a backend, so the chip is the child's.

Appends one JSON line per config to stdout. Every line names the device it
was taken on; ``mfu`` (and the BEST line built on it) only appears in
records taken on a TPU.
"""

import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


DEFAULT = [
    # [seq_len, micro_bs, attention_impl, remat_policy]: the ctx policy
    # (see docs/performance.md) + impl A/B
    [2048, 8, "xla_twopass", "ctx"],
    [4096, 4, "xla_twopass", "ctx"],
    [4096, 8, "xla_twopass", "ctx"],
    [2048, 2, "xla_twopass", "dots"],
    [2048, 8, "xla", "ctx"],
    [2048, 4, "xla_twopass", "ctx", "qwen3_1p7b", "muon"],
    [4096, 2, "xla_twopass", "ctx", "qwen3_1p7b", "muon"],
    [2048, 8, "pallas_flash", "ctx"],
]

_CHILD = """
import json, os, sys
sys.path.insert(0, {root!r})
from bench import run_bench
r = run_bench({seq}, {mb}, {steps}, attention_impl={attn!r},
              remat_policy={remat!r}, preset={preset!r}, optimizer={opt!r})
print("SWEEPRESULT " + json.dumps(r), flush=True)
"""


def _norm(seq_len, micro_bs, attn, remat, preset, opt):
    return dict(seq_len=seq_len, micro_bs=micro_bs, attention=attn,
                remat_policy=remat, preset=preset, optimizer=opt)


def _error_record(base, msg: str) -> dict:
    import re

    msg = re.sub(r"\x1b\[[0-9;]*m", "", msg)  # strip ANSI
    oom = re.search(r"Ran out of memory.*?hbm capacity by [0-9.]+\w", msg)
    return {**base, "error": oom.group(0) if oom else msg[-600:]}


def _run_subprocess(seq_len, micro_bs, steps, attn, remat, preset, opt):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = _CHILD.format(root=root, seq=seq_len, mb=micro_bs, steps=steps,
                         attn=attn, remat=remat, preset=preset, opt=opt)
    base = _norm(seq_len, micro_bs, attn, remat, preset, opt)
    timeout = float(os.environ.get("SWEEP_CONFIG_TIMEOUT_S", 1500))
    try:
        p = subprocess.run([sys.executable, "-c", code], timeout=timeout,
                           capture_output=True, text=True)
    except subprocess.TimeoutExpired as e:
        tail = ((e.stderr or b"").decode() if isinstance(e.stderr, bytes)
                else (e.stderr or ""))[-300:]
        return {**base, "error": f"HANG >{int(timeout)}s (killed); {tail}"}
    for line in p.stdout.splitlines():
        if line.startswith("SWEEPRESULT "):
            return json.loads(line[len("SWEEPRESULT "):])
    return _error_record(base, p.stderr or p.stdout or f"exit {p.returncode}")


def main():
    from veomni_tpu.utils.xla_flags import apply_performance_flags

    apply_performance_flags()
    configs = json.loads(os.environ.get("SWEEP_CONFIGS", "null")) or DEFAULT
    steps = int(os.environ.get("SWEEP_STEPS", 8))
    use_subprocess = os.environ.get("SWEEP_SUBPROCESS") == "1"
    results = []
    for seq_len, micro_bs, attn, remat, *extra in configs:
        preset = extra[0] if extra else "qwen3_0p6b"
        opt = extra[1] if len(extra) > 1 else "adamw"
        if use_subprocess:
            r = _run_subprocess(int(seq_len), int(micro_bs), steps,
                                attn, remat, preset, opt)
        else:
            from bench import run_bench

            try:
                r = run_bench(int(seq_len), int(micro_bs), steps,
                              attention_impl=attn, remat_policy=remat,
                              preset=preset, optimizer=opt)
            except Exception as e:  # OOM etc: record and continue the ladder
                r = _error_record(
                    _norm(seq_len, micro_bs, attn, remat, preset, opt), str(e)
                )
        results.append(r)
        print(json.dumps(r), flush=True)
    ok = [r for r in results if "mfu" in r]
    if ok:
        best = max(ok, key=lambda r: r["mfu"])
        print("BEST:", json.dumps(best), flush=True)


if __name__ == "__main__":
    main()
