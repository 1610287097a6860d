#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the source pipeline and the server once, through the entry points a
user would call, at the full width of GPT-2 124M (``preset gpt2``: 768 wide,
12 layers, 12 heads, vocabulary 50,257, 1,024 positions, bf16 activations,
global batch 8 x 1,024, ``lm_synth`` data from a seed; weights random):

1. train   ``flows/gpt_flow.py run``: two epochs, two committed saves
2. resume  ``flows/gpt_flow.py run --from-run``: full sharded state restored
3. eval    ``flows/gpt_eval_flow.py run``: finite perplexity, a card on disk
4. serve   checkpoint -> paged ``ServeEngine`` -> ``serve_forever`` ->
           ``POST /generate`` over HTTP; compile_stats() fixed after warmup
5. kernels each Pallas kernel compiled (``interpret=False``) against XLA

    python chip_smoke.py                  # needs a TPU; fails at once without
    python chip_smoke.py --rehearse-cpu   # same path, `test` preset, CPU

The process that runs this file never imports jax: a chip belongs to one
process at a time, so every stage is a child that takes the chip in turn and
gives it back. The last line of stdout is one JSON object; ``"ok": true`` is
only ever printed for a run on ``platform=tpu``. Any stage that fails makes
the exit code non-zero.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "CHIP_SMOKE_RESULT "
DEADLINE_S = 1150.0  # the contract is 1200 s, compilation included

# Full width on the chip; the rehearsal cuts width and depth to the `test`
# preset so the same code path runs in minutes on CPU devices.
CHIP = dict(
    preset="gpt2", dtype="bfloat16", batch=8, seq_len=1024, steps=6,
    slots=8, buckets=[64, 512], prompt_lens=[12, 200, 512], max_new=16,
    spec=4, flash=dict(B=8, T=1024, H=12, D=64),
    int8=[(768, 2304, False), (3072, 768, False), (768, 3072, True)],
)
REHEARSAL = dict(
    preset="test", dtype="", batch=8, seq_len=64, steps=3,
    slots=4, buckets=[16, 64], prompt_lens=[5, 12, 40], max_new=8,
    spec=2, flash=dict(B=1, T=64, H=2, D=16),
    int8=[(128, 256, False), (256, 128, False), (128, 256, True)],
)


class StageFailed(Exception):
    pass


# ----------------------------------------------------------------- parent
class Smoke:
    def __init__(self, args):
        self.rehearse = args.rehearse_cpu
        self.size = REHEARSAL if self.rehearse else CHIP
        self.resume_mesh = args.resume_mesh
        self.log_dir = os.path.abspath(args.log_dir)
        self.device: dict | None = None
        self.t0 = time.monotonic()
        self.child: subprocess.Popen | None = None
        self.report: dict = {}
        self.had_jax = "jax" in sys.modules  # only ever under a test

    # Every line names the device: a CPU line can never be read as a chip's.
    def say(self, msg: str) -> None:
        d = self.device or {"platform": "?", "kind": "?", "count": "?"}
        print(
            f"[chip_smoke platform={d['platform']} "
            f"device_kind={d['kind']!r} devices={d['count']}] {msg}",
            flush=True,
        )

    def env(self) -> dict:
        env = dict(os.environ)
        env.update(
            TPUFLOW_HOME=os.path.join(self.work, "home"),
            TPUFLOW_DATA_DIR=os.path.join(self.work, "data"),
            PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
        )
        if self.rehearse:
            env.update(JAX_PLATFORMS="cpu", TPUFLOW_FORCE_CPU="1")
        return env

    def run_child(self, name: str, argv: list[str]) -> str:
        """Run one stage's process to its end; return its output. Raises
        StageFailed on a non-zero exit or when the time budget runs out."""
        left = DEADLINE_S - (time.monotonic() - self.t0)
        if left <= 0:
            raise StageFailed(f"{name}: no time left in the {DEADLINE_S:.0f}s budget")
        log_path = os.path.join(self.log_dir, f"{name}.log")
        t0 = time.monotonic()
        with open(log_path, "w") as log:
            self.child = subprocess.Popen(
                [sys.executable, *argv], cwd=REPO, env=self.env(),
                stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
            )
            try:
                rc = self.child.wait(timeout=left)
            except subprocess.TimeoutExpired:
                self.stop_child()
                raise StageFailed(
                    f"{name}: still running when the {DEADLINE_S:.0f}s budget "
                    f"ran out (log: {log_path})"
                ) from None
            finally:
                self.child = None
        with open(log_path, errors="replace") as f:
            out = f.read()
        for line in out.splitlines():
            if line.startswith(("[gpt", "[tpuflow] run", "[smoke]")):
                self.say(f"{name}| {line}")
        self.say(f"{name}: exit {rc} after {time.monotonic() - t0:.1f}s")
        if rc != 0:
            for line in out.splitlines()[-40:]:
                self.say(f"{name}! {line}")
            raise StageFailed(f"{name}: exit code {rc} (log: {log_path})")
        return out

    def stop_child(self) -> None:
        if self.child is not None and self.child.poll() is None:
            try:
                os.killpg(self.child.pid, signal.SIGKILL)
            except OSError:
                pass
            self.child.wait()

    def stage_child(self, name: str, ctx: dict) -> dict:
        """A stage implemented in this file, run as a child that may
        import jax; its result is the tagged JSON line it prints last."""
        ctx = {**ctx, "rehearse": self.rehearse, "size": self.size}
        out = self.run_child(
            name,
            [os.path.abspath(__file__), "--stage", name, "--ctx", json.dumps(ctx)],
        )
        lines = [ln for ln in out.splitlines() if ln.startswith(RESULT_TAG)]
        if not lines:
            raise StageFailed(f"{name}: printed no result")
        return json.loads(lines[-1][len(RESULT_TAG):])

    # ------------------------------------------------------------ stages
    def stage_device(self) -> None:
        dev = self.stage_child("device", {})
        self.device = {k: dev[k] for k in ("platform", "kind", "count")}
        self.say(f"jax {dev['jax']} jaxlib {dev['jaxlib']}")
        if self.rehearse:
            if dev["platform"] != "cpu":
                raise StageFailed("rehearsal asked for the CPU, got " + dev["platform"])
        elif dev["platform"] != "tpu":
            raise StageFailed(
                f"JAX found platform={dev['platform']!r}, not a TPU; "
                "`--rehearse-cpu` is the CPU mode"
            )

    def stage_native(self) -> None:
        """The native checkpoint writer is git-ignored and built from
        io.cpp on first use; a machine that can build it must."""
        from tpuflow import _native

        built = _native.lib() is not None
        can_build = bool(shutil.which("make") and shutil.which("g++"))
        self.say(f"native: libtpuflow_io {'loaded' if built else 'NOT built'}")
        if not built and can_build:
            raise StageFailed("native: make and g++ are here but the build failed")
        self.report["ckpt_writer"] = "native" if built else "numpy"

    def flow_args(self, data: int, fsdp: int) -> list[str]:
        s = self.size
        argv = [
            "--preset", s["preset"], "--epochs", "2",
            "--steps-per-epoch", str(s["steps"]),
            "--batch-size", str(s["batch"]), "--seq-len", str(s["seq_len"]),
            "--data-axis", str(data), "--fsdp-axis", str(fsdp),
            "--dataset", "lm_synth", "--attn-impl", "auto",
        ]
        return argv + (["--dtype", s["dtype"]] if s["dtype"] else [])

    def check_train_run(self, name: str, out: str, first_step: int) -> dict:
        """One gpt_flow run: succeeded, finite losses, two committed saves
        whose step counters continue from ``first_step``."""
        m = re.search(r"\[tpuflow\] run (TpuGptTrain/\d+) succeeded", out)
        if not m:
            raise StageFailed(f"{name}: no succeeded run in the output")
        run = m.group(1)
        epochs = re.findall(
            r"\[gpt\] epoch (\d+): loss=(\S+) val_loss=(\S+) ppl=(\S+)"
            r"(?: \((\d+) tok/s\))?", out,
        )
        losses = [float(x) for e in epochs for x in e[1:3]]
        if len(epochs) != 2 or not all(math.isfinite(x) for x in losses):
            raise StageFailed(f"{name}: want 2 epochs of finite losses, got {epochs}")
        writer = re.search(r"shard writer (\w+)", out)
        if not writer or writer.group(1) != self.report["ckpt_writer"]:
            raise StageFailed(
                f"{name}: checkpoint stage wrote with {writer and writer.group(1)}, "
                f"expected the {self.report['ckpt_writer']} writer"
            )
        run_dir = os.path.join(self.work, "home", "flows", run)
        steps = sorted(
            int(os.path.basename(d)[len("step_"):])
            for d in glob.glob(
                os.path.join(run_dir, "tpu_storage", "train", "checkpoints", "step_*")
            )
            if os.path.exists(os.path.join(d, "metadata.json"))
        )
        n = self.size["steps"]
        if steps != [first_step + n, first_step + 2 * n]:
            raise StageFailed(
                f"{name}: committed checkpoint steps {steps}, want "
                f"{[first_step + n, first_step + 2 * n]}"
            )
        first = re.search(r"\[gpt\] first step .* in (\S+)s", out)
        peaks = {}
        for path in glob.glob(os.path.join(run_dir, "train", "*", "profile.json")):
            with open(path) as f:
                for sample in json.load(f)["samples"]:
                    for d in sample["devices"]:
                        if d.get("peak_bytes_in_use") is not None:
                            peaks[d["id"]] = max(
                                peaks.get(d["id"], 0), d["peak_bytes_in_use"]
                            )
        rec = {
            "run": run, "checkpoint_steps": steps,
            "losses": [float(e[1]) for e in epochs],
            "tokens_per_s": [int(e[4]) for e in epochs if e[4]],
            "first_step_s": float(first.group(1)) if first else None,
            "peak_bytes_per_device": [peaks[k] for k in sorted(peaks)] or None,
        }
        self.say(f"{name}: {json.dumps(rec)}")
        return rec

    def stage_train(self) -> None:
        out = self.run_child(
            "train",
            [os.path.join(REPO, "flows", "gpt_flow.py"), "run",
             *self.flow_args(1, self.device["count"])],
        )
        self.report["train"] = self.check_train_run("train", out, 0)

    def stage_resume(self) -> None:
        data, fsdp = 1, self.device["count"]
        if self.resume_mesh:
            axes = dict(kv.split("=") for kv in self.resume_mesh.split(","))
            data, fsdp = int(axes["data"]), int(axes["fsdp"])
        src = self.report["train"]
        out = self.run_child(
            "resume",
            [os.path.join(REPO, "flows", "gpt_flow.py"), "run",
             "--from-run", src["run"], *self.flow_args(data, fsdp)],
        )
        if "[gpt] full sharded state restored" not in out:
            raise StageFailed("resume: no '[gpt] full sharded state restored' line")
        rec = self.check_train_run("resume", out, src["checkpoint_steps"][-1])
        rec["mesh"] = {"data": data, "fsdp": fsdp}
        self.report["resume"] = rec

    def stage_eval(self) -> None:
        out = self.run_child(
            "eval",
            [os.path.join(REPO, "flows", "gpt_eval_flow.py"), "run",
             "--checkpoint-run-pathspec", self.report["train"]["run"],
             "--batch-size", str(self.size["batch"]), "--sample-tokens", "8"],
        )
        m = re.search(r"\[gpt_eval\] test loss=(\S+) ppl=(\S+)", out)
        run = re.search(r"\[tpuflow\] run (TpuGptEval/\d+) succeeded", out)
        if not (m and run and math.isfinite(float(m.group(2)))):
            raise StageFailed("eval: no finite perplexity from a succeeded run")
        card = os.path.join(
            self.work, "home", "flows", run.group(1), "start", "0", "card.html"
        )
        if not (os.path.exists(card) and os.path.getsize(card) > 0):
            raise StageFailed(f"eval: no card at {card}")
        self.report["eval"] = {"ppl": float(m.group(2)), "card_bytes": os.path.getsize(card)}
        self.say(f"eval: {json.dumps(self.report['eval'])}")

    def stage_serve(self) -> None:
        self.report["serve"] = self.stage_child(
            "serve", {"train_run": self.report["train"]["run"]}
        )
        self.say(f"serve: {json.dumps(self.report['serve'])}")

    def stage_kernels(self) -> None:
        self.report["kernels"] = self.stage_child("kernels", {})
        self.say(f"kernels: {json.dumps(self.report['kernels'])}")

    # --------------------------------------------------------------- run
    def cache_entries(self) -> tuple[str, int]:
        # tpuflow.dist.COMPILE_CACHE_DIR, spelled out: importing it would
        # import jax into the parent.
        d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
            REPO, ".compile_cache"
        )
        return d, len(glob.glob(os.path.join(d, "*-cache")))

    def run(self) -> int:
        os.makedirs(self.log_dir, exist_ok=True)
        self.work = tempfile.mkdtemp(prefix="chip_smoke_")
        cache_dir, before = self.cache_entries()
        failed = None
        try:
            for name in STAGES:
                getattr(self, f"stage_{name}")()
                self.say(f"stage {name}: passed")
        except StageFailed as e:
            failed = str(e)
            self.say(f"FAILED {failed}")
        finally:
            self.stop_child()
            shutil.rmtree(self.work, ignore_errors=True)
        after = self.cache_entries()[1]
        # Set-up time: what a cold start pays before useful work — the
        # train and resume first steps and the server's warm-up.
        setup = [
            self.report.get("train", {}).get("first_step_s"),
            self.report.get("resume", {}).get("first_step_s"),
            self.report.get("serve", {}).get("warmup_s"),
        ]
        self.report["compile_cache"] = {
            "dir": cache_dir, "entries_before": before, "entries_after": after,
            "setup_s": round(sum(setup), 1) if None not in setup else None,
        }
        self.report["wall_s"] = round(time.monotonic() - self.t0, 1)
        self.say(f"compile cache: {json.dumps(self.report['compile_cache'])}")
        self.say(f"wall {self.report['wall_s']}s")
        if ("jax" in sys.modules) != self.had_jax:
            raise AssertionError("the parent imported jax: it would hold the chip")
        if failed is not None:
            return 1
        with open(os.path.join(self.log_dir, "report.json"), "w") as f:
            json.dump({"device": self.device, **self.report}, f, indent=1)
        # "ok" is a statement about the chip; a rehearsal never makes it.
        result = {"ok": not self.rehearse, "device": self.device}
        if self.rehearse:
            result["rehearsal"] = "passed"
        print(json.dumps(result), flush=True)
        return 0


STAGES = ("device", "native", "train", "resume", "eval", "serve", "kernels")


# --------------------------------------------------------------- children
def _child_jax(ctx: dict):
    """First jax touch of a stage child: the platform (CPU only when this
    is the rehearsal) and the persistent compile cache."""
    from tpuflow import dist

    if ctx["rehearse"]:
        dist.force_cpu_platform(8)
    dist.maybe_enable_compile_cache()
    import jax

    return jax


def _peak_bytes(jax) -> list | None:
    stats = [d.memory_stats() for d in jax.local_devices()]
    if not all(stats):
        return None
    return [s.get("peak_bytes_in_use") for s in stats]


def child_device(ctx: dict) -> dict:
    jax = _child_jax(ctx)
    import jaxlib

    d = jax.devices()[0]
    return {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices()),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
    }


def child_serve(ctx: dict) -> dict:
    jax = _child_jax(ctx)
    import threading
    import urllib.request

    import jax.numpy as jnp
    import numpy as np

    from tpuflow import obs
    from tpuflow.ckpt import restore_from_handle
    from tpuflow.flow import Run
    from tpuflow.infer import generate
    from tpuflow.infer.serve import ServeEngine, serve_forever
    from tpuflow.models.gpt2 import GPT2, GPT2Config

    s = ctx["size"]
    run = Run(ctx["train_run"])
    mc = dict(run.data.model_config)
    cfg = GPT2Config(
        dropout=0.0, attn_impl="auto",
        dtype=jnp.dtype(s["dtype"] or "float32"), **mc,
    )
    model = GPT2(cfg)
    params = restore_from_handle(
        run.data.result_checkpoint, weights_only=True, zero_copy=run.successful
    )
    params = jax.tree_util.tree_map(jnp.asarray, params)
    engine = ServeEngine(
        model, params, max_slots=s["slots"], buckets=s["buckets"],
        decode_block=8, speculative=s["spec"],
    )
    t0 = time.monotonic()
    warm = engine.warmup()
    warmup_s = time.monotonic() - t0
    print(f"[smoke] serve warmup {warmup_s:.1f}s compile_stats {warm}", flush=True)

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=(n,), dtype=np.int32)
        for n in s["prompt_lens"] for _ in range(2)
    ]
    stop = threading.Event()
    loop = threading.Thread(
        target=serve_forever, args=(engine,),
        kwargs={"max_s": 600.0, "should_stop": stop.is_set}, daemon=True,
    )
    loop.start()
    answers: list = [None] * len(prompts)
    try:
        t_url = time.monotonic() + 30.0
        while obs.goodput_live().serve_generate_url is None:
            if time.monotonic() > t_url or not loop.is_alive():
                raise RuntimeError("serve_forever never advertised /generate")
            time.sleep(0.02)
        url = obs.goodput_live().serve_generate_url

        def post(i: int) -> None:
            body = json.dumps({
                "id": f"smoke-{i}", "prompt": prompts[i].tolist(),
                "max_new_tokens": s["max_new"],
            }).encode()
            req = urllib.request.Request(
                url, data=body, headers={"Content-Type": "application/json"}
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                answers[i] = json.loads(r.read())

        clients = [threading.Thread(target=post, args=(i,)) for i in range(len(prompts))]
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=180)
    finally:
        stop.set()
        loop.join(timeout=60)
    if loop.is_alive():
        raise RuntimeError("serve_forever did not stop")
    unanswered = [i for i, a in enumerate(answers) if not a or "tokens" not in a]
    if unanswered:
        raise RuntimeError(f"requests not answered over HTTP: {unanswered}")
    for a in answers:
        toks = a["tokens"]
        if len(toks) != s["max_new"] or not all(0 <= t < cfg.vocab_size for t in toks):
            raise RuntimeError(f"bad tokens in answer {a['id']}: {toks}")
    after = engine.compile_stats()
    if after != warm:
        raise RuntimeError(f"compile_stats grew after warm-up: {warm} -> {after}")

    # Agreement with solo greedy decoding, as a number: on the near-uniform
    # logits of a barely trained model a tie can flip with the batch width,
    # so this is reported and not a pass/fail of the smoke.
    same_req = same_tok = 0
    for j in range(0, len(prompts), 2):
        solo = np.asarray(generate(
            model, params, np.stack(prompts[j:j + 2]),
            max_new_tokens=s["max_new"], temperature=0.0,
        ))
        for k in range(2):
            got = np.asarray(answers[j + k]["tokens"])
            same_req += int(np.array_equal(got, solo[k]))
            same_tok += int((got == solo[k]).sum())
    return {
        "requests": len(prompts), "answered": len(prompts) - len(unanswered),
        "prompt_lens": s["prompt_lens"], "max_new_tokens": s["max_new"],
        "compile_stats": after, "warmup_s": round(warmup_s, 1),
        "solo_agreement_requests": round(same_req / len(prompts), 3),
        "solo_agreement_tokens": round(same_tok / (len(prompts) * s["max_new"]), 3),
        "spec_tokens_per_verify": engine.spec_accept_rate,
        "peak_bytes_per_device": _peak_bytes(jax),
    }


def child_kernels(ctx: dict) -> dict:
    jax = _child_jax(ctx)
    import jax.numpy as jnp
    import numpy as np

    import importlib

    from tpuflow.ops import flash_attention as fa
    from tpuflow.ops.attention import resolve_attention_impl, xla_attention

    # tpuflow.ops re-exports the int8_matmul FUNCTION under the module's name.
    i8 = importlib.import_module("tpuflow.ops.int8_matmul")

    s = ctx["size"]
    # interpret is ASSERTED, not inferred: off only where the Mosaic
    # compiler is the one that has to accept the kernel.
    interpret = ctx["rehearse"]
    assert interpret == (jax.default_backend() != "tpu")
    out: dict = {"interpret": interpret}

    f = s["flash"]
    shape = (f["B"], f["T"], f["H"], f["D"])
    dt = jnp.float32 if ctx["rehearse"] else jnp.bfloat16
    tol = 1e-4 if ctx["rehearse"] else 1.6e-2
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(kk, shape, dt) for kk in keys)

    def kernels(q, k, v, g):
        o, lse = fa._flash_fwd(q, k, v, True, interpret, with_lse=True)
        return o, fa._flash_bwd_fused(q, k, v, o, lse, g, True, interpret)

    def reference(q, k, v, g):
        o, vjp = jax.vjp(lambda q, k, v: xla_attention(q, k, v, causal=True), q, k, v)
        return o, vjp(g)

    o, fused = jax.jit(kernels)(q, k, v, g)
    o_ref, g_ref = jax.jit(reference)(q, k, v, g)

    def err(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / max(1.0, float(np.max(np.abs(b)))))

    flash = {
        "shape": shape, "dtype": str(jnp.dtype(dt)), "tolerance": tol,
        "blocks": fa._block_sizes(f["T"], f["T"]),
        "heads_per_program": fa._head_group(f["H"], f["D"]),
        "fwd": err(o, o_ref),
        "bwd_fused": max(err(a, b) for a, b in zip(fused, g_ref)),
    }
    out["flash"] = flash
    print(f"[smoke] flash {flash}", flush=True)
    bad = [n for n in ("fwd", "bwd_fused") if not flash[n] <= tol]
    if bad:
        raise RuntimeError(f"flash kernels out of tolerance {tol}: {bad} {flash}")

    m = s["slots"]
    out["int8"] = []
    for kdim, n, contract_last in s["int8"]:
        kx, kw = jax.random.split(jax.random.PRNGKey(kdim + n))
        x = jax.random.normal(kx, (m, kdim), jnp.float32).astype(dt)
        w = jax.random.normal(kw, (n, kdim) if contract_last else (kdim, n))
        wq, ws = i8.quantize_rows(w if contract_last else w.T)
        wq = wq if contract_last else wq.T
        kw_args = dict(w_contract_last=contract_last, out_dtype=jnp.float32)
        got = jax.jit(lambda x, wq, ws: i8._pallas_int8_matmul(
            x, wq, ws.reshape(-1), interpret=interpret, **kw_args))(x, wq, ws)
        ref = jax.jit(lambda x, wq, ws: i8._xla_int8_matmul(
            x, wq, ws.reshape(-1), **kw_args))(x, wq, ws)
        rec = {
            "m_k_n": (m, kdim, n), "w_contract_last": contract_last,
            "err": err(got, ref),
            "bit_equal": bool(np.array_equal(np.asarray(got), np.asarray(ref))),
        }
        out["int8"].append(rec)
        print(f"[smoke] int8 {rec}", flush=True)
        if not rec["err"] <= 1e-5:
            raise RuntimeError(f"int8 pallas kernel disagrees with XLA: {rec}")

    # What `auto` picks at this model's shapes on this backend.
    T, W = s["seq_len"], max(s["buckets"])
    out["auto"] = {
        f"attention_train_T{T}": resolve_attention_impl(
            "auto", (f["B"], T, f["H"], f["D"]), T
        ),
        f"attention_prefill_T{W}": resolve_attention_impl(
            "auto", (1, W, f["H"], f["D"]), W
        ),
        "int8": {
            str((m, kdim, n)): i8.resolve_int8_impl(m, kdim, n)
            for kdim, n, _ in s["int8"]
        },
    }
    # The train stage ran with --attn-impl auto: on one chip that is the
    # kernel checked above from 1,024 positions (ISSUE 31), off the chip
    # XLA — and XLA under a mesh of several chips too, which this child
    # does not set: a Mosaic kernel cannot be partitioned.
    want = "xla" if ctx["rehearse"] or T < 1024 else "flash"
    if out["auto"][f"attention_train_T{T}"] != want:
        raise RuntimeError(f"auto chose {out['auto']} for training, not {want}")
    # The form compiled.cost_analysis() takes on this backend (a dict or a
    # list of dicts has differed by version; obs/device.py reads it).
    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
    out["cost_analysis_type"] = type(compiled.cost_analysis()).__name__
    out["peak_bytes_per_device"] = _peak_bytes(jax)
    return out


CHILDREN = {"device": child_device, "serve": child_serve, "kernels": child_kernels}


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="run the same path at the `test` preset on CPU "
                        "devices; prints platform=cpu and never \"ok\": true")
    p.add_argument("--resume-mesh", default="",
                   help="resume under another layout, e.g. data=2,fsdp=2 "
                        "(default: the layout trained on, fsdp = devices)")
    p.add_argument("--log-dir",
                   default=os.path.join(REPO, "chiprun_out", "chip_smoke"),
                   help="where each stage's full output and report.json go")
    p.add_argument("--stage", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    p.add_argument("--ctx", default="{}", help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if not os.path.isfile(os.path.join(REPO, "flows", "gpt_flow.py")) or not os.path.isdir(
        os.path.join(REPO, "tpuflow")
    ):
        print(f"chip_smoke: {REPO} is not a tpuflow checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    if args.stage:
        result = CHILDREN[args.stage](json.loads(args.ctx))
        print(RESULT_TAG + json.dumps(result), flush=True)
        return 0
    return Smoke(args).run()


if __name__ == "__main__":
    sys.exit(main())
