#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
full width of the one full-width model the repo supports (VGG, batch 512
per chip; weights random from a seed, depth of the run cut to 8 steps):

    gather     python -m ddp_tpu.ops.gather       Pallas row gather == table[idx]
    attention  python -m ddp_tpu.ops.attention    the token models' attention kernels
                                                  against the XLA loops and float32
    ssd        python -m ddp_tpu.ops.ssd          the token model's scan kernels
                                                  against the XLA path and float32
    selscan    python -m ddp_tpu.ops.selscan      the second token model's scan kernels
                                                  against the XLA path and float32
    moe        python -m ddp_tpu.models.moe       the routed experts' products over the live
                                                  row tiles against the every-tile form
    sambay     python -m ddp_tpu.models.sambay    one step of the SambaY stage (layers
                                                  14-19 of 32): tiny, then published widths
    train      python singlegpu.py 1 1 ...        8 steps, checkpoint, final eval
    train_again  the same command once more       adds no compile-cache entries
    serve      python -m ddp_tpu.serve            /predict x3, SIGTERM drain, exit 0
    bf16 / resident / shard_update                the flags that change the program
    resume     python singlegpu.py 2 1 --resume   picks up at epoch 1
    lm         python -m ddp_tpu.train.lm         compile coverage (64-wide preset)
    generate   python -m ddp_tpu.serve --generate /generate x3, drain, exit 0

(``multigpu.py`` over every chip when the machine shows more than one.
``python chip_smoke.py sambay lm`` runs the phases named and no others.)

This parent imports neither jax nor ddp_tpu: every phase is a child that
owns the chip while it runs and releases it when it exits.  Children get
``JAX_PLATFORMS=tpu``, so a backend cannot come up on CPU; they share one
compile cache (``JAX_COMPILATION_CACHE_DIR`` if set, else the checkout's
``.jax_cache`` — ddp_tpu/utils/platform.py) and one output directory
(``.smoke_out/``), and none writes into the repo root.

Any failed check ends the run with a non-zero exit, the failing command and
the tail of its output, and no result line.  On success the last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX
reported the device to the children.  Wall times printed here are smoke
timings, not metrics.
"""
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, ".smoke_out")
PY = sys.executable

PHASE_TIMEOUT_S = 600
STEPS = 8                      # per epoch, per chip count: size = 8 x batch x chips
LN10_BAND = (2.30, 2.42)       # first-step CE, 10 classes; CPU run of seed 0: 2.3597
LN256_BAND = (5.0, 6.2)        # first-step CE of the byte LM; ln 256 = 5.545
SAMBAY_CONFIG = os.path.join("benchmark", "configs",
                             "phi4_mini_flash_stage14_19.json")

DEVICE_RE = re.compile(
    r'^device: platform=(\S+) device_kind="([^"]*)" visible=(\d+) '
    r'mesh=(\S+) ids=(\S+)(.*)$', re.M)


class SmokeFailure(Exception):
    pass


def fail(what: str, cmd=None, output: str = "") -> None:
    lines = [f"FAILED: {what}"]
    if cmd:
        lines.append("command: " + " ".join(cmd))
    if output:
        lines.append("--- tail of its output ---\n" + output[-3000:])
    raise SmokeFailure("\n".join(lines))


class Smoke:
    """One smoke run: the child environment, what the device line must
    say, and the verdicts so far."""

    def __init__(self, platform: str, model: str, batch: int, out: str,
                 loss_band=LN10_BAND):
        self.platform, self.model, self.batch = platform, model, batch
        self.out, self.loss_band = out, loss_band
        self.env = dict(os.environ, JAX_PLATFORMS=platform,
                        # cache every compile, so "the second run adds no
                        # entries" does not hang on a one-second threshold
                        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
        # The program's rule (utils/platform.py), restated because this
        # parent imports nothing of the program.  Should the two part, the
        # directory counted here stays empty and train_again fails.
        self.cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or os.path.join(ROOT, ".jax_cache"))
        self.device = None         # (platform, kind, count) of the first child
        self.verdicts = []         # (phase, wall_s, note)

    # -- plumbing ----------------------------------------------------------

    def cache_entries(self) -> int:
        try:
            return len(os.listdir(self.cache_dir))
        except FileNotFoundError:
            return 0

    def path(self, name: str) -> str:
        return os.path.join(self.out, name)

    def run(self, cmd) -> str:
        """Run one child to its end; its combined output, or a failure."""
        try:
            p = subprocess.run(cmd, cwd=ROOT, env=self.env, text=True,
                               stdout=subprocess.PIPE,
                               stderr=subprocess.STDOUT,
                               timeout=PHASE_TIMEOUT_S)
        except subprocess.TimeoutExpired as e:
            tail = e.stdout or ""
            if isinstance(tail, bytes):
                tail = tail.decode(errors="replace")
            fail(f"no exit within {PHASE_TIMEOUT_S}s", cmd, tail)
        if p.returncode != 0:
            fail(f"exit code {p.returncode}", cmd, p.stdout)
        return p.stdout

    def check_device(self, cmd, output: str) -> dict:
        """The child's start-up line must name the expected platform and
        the same device every other child saw."""
        m = DEVICE_RE.search(output)
        if not m:
            fail("no 'device:' start-up line", cmd, output)
        platform, kind, visible, mesh, ids, rest = m.groups()
        if platform != self.platform:
            fail(f"device line says platform={platform}, expected "
                 f"{self.platform}", cmd, m.group(0))
        seen = (platform, kind, int(visible))
        if self.device is None:
            self.device = seen
        elif seen != self.device:
            fail(f"device changed between children: {self.device} then "
                 f"{seen}", cmd, m.group(0))
        ids = ids.split(",")
        if len(set(ids)) != len(ids):
            fail("mesh lists a device id twice", cmd, m.group(0))
        return {"mesh": mesh, "ids": ids,
                "fields": dict(f.split("=", 1) for f in rest.split())}

    def phase(self, name: str, fn) -> None:
        t0 = time.time()
        note = fn()
        self.verdicts.append((name, time.time() - t0, note or ""))
        print(f"[smoke] {name}: ok ({self.verdicts[-1][1]:.1f}s smoke "
              f"timing) {note or ''}", flush=True)

    # -- training ----------------------------------------------------------

    def train(self, tag: str, *flags, epochs=1, expect_epoch=0,
              snapshot=None, native_augment="on") -> str:
        chips = self.device[2]
        entry = "singlegpu.py" if chips == 1 else "multigpu.py"
        cmd = [PY, entry, str(epochs), "1", "--model", self.model,
               "--synthetic", "--synthetic_size",
               str(STEPS * self.batch * chips),
               "--batch_size", str(self.batch),
               "--snapshot_path", snapshot or self.path(f"{tag}.pt"),
               "--metrics_path", self.path(f"{tag}.jsonl"), *flags]
        out = self.run(cmd)
        dev = self.check_device(cmd, out)
        if len(dev["ids"]) != chips:
            fail(f"mesh holds {len(dev['ids'])} device(s), machine shows "
                 f"{chips}", cmd, out)
        if dev["fields"].get("native_augment") != native_augment:
            fail("start-up line says native_augment="
                 f"{dev['fields'].get('native_augment')}, expected "
                 f"{native_augment} (on: the host kernel built; n/a: the "
                 "run augments on device)", cmd, out)
        if f"Epoch {expect_epoch} | Batchsize: {self.batch} | Steps: " \
                f"{STEPS}\n" not in out:
            fail(f"no 'Epoch {expect_epoch} | Batchsize: {self.batch} | "
                 f"Steps: {STEPS}' line: the epoch is not synthetic_size / "
                 f"({self.batch} x {chips} chips)", cmd, out)
        with open(self.path(f"{tag}.jsonl")) as f:
            recs = [json.loads(line) for line in f]
        steps = [r for r in recs if "loss" in r]
        if [r["epoch"] for r in steps] != [expect_epoch] * STEPS:
            fail(f"expected {STEPS} loss records of epoch {expect_epoch}, "
                 f"got epochs {[r['epoch'] for r in steps]}", cmd, out)
        if [r["step"] for r in steps] != list(
                range(expect_epoch * STEPS, (expect_epoch + 1) * STEPS)):
            fail(f"step numbers {[r['step'] for r in steps]} do not "
                 f"continue from epoch {expect_epoch}", cmd, out)
        losses = [r["loss"] for r in steps]
        if not all(math.isfinite(v) for v in losses):
            fail(f"non-finite loss in {losses}", cmd, out)
        if expect_epoch == 0 and not (
                self.loss_band[0] <= losses[0] <= self.loss_band[1]):
            fail(f"first-step loss {losses[0]} outside {self.loss_band} "
                 "(ln 10 = 2.303)", cmd, out)
        final = [r for r in recs if r.get("final")]
        if len(final) != 1 or not 0.0 <= final[0]["eval_accuracy"] <= 100.0:
            fail(f"no final eval record in {tag}.jsonl", cmd, out)
        if epochs > 1 and "Resuming training from snapshot at Epoch " \
                f"{expect_epoch - 1}" not in out:
            fail("resumed run did not say it resumed", cmd, out)
        return (f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, eval "
                f"{final[0]['eval_accuracy']:.2f}%, mesh {dev['mesh']} "
                f"ids {','.join(dev['ids'])}")

    def train_dp(self, tag: str) -> str:
        """The plain data-parallel run.  Over several chips it also runs
        the cross-replica drift audit, which must find every replica's
        parameters bit-identical after real all-reduces."""
        if self.device[2] == 1:
            return self.train(tag)
        note = self.train(tag, "--drift_audit_every", "2",
                          "--drift_action", "abort")
        with open(self.path(f"{tag}.jsonl.prom")) as f:
            prom = f.read()
        count = {k: float(v) for k, v in re.findall(
            r"^ddp_drift_(audits|detections)_total(?:\{[^}]*\})? (\S+)$",
            prom, re.M)}
        if not count.get("audits", 0) >= 3 or count.get("detections") != 0:
            fail(f"drift audit over {self.device[2]} replicas: {count} "
                 f"(want >= 3 audits, 0 detections) in {tag}.jsonl.prom")
        return f"{note}, {count['audits']:.0f} drift audits clean"

    def train_again(self) -> str:
        """The train child once more against the same cache directory.
        The first one must have left its executables there, or "adds no
        entries" would hold of no cache at all."""
        before = self.cache_entries()
        if before == 0:
            fail(f"compile cache {self.cache_dir} is empty after the first "
                 "train child: the children do not keep their executables "
                 "where the smoke counts them")
        note = self.train_dp("train_again")
        added = self.cache_entries() - before
        if added:
            fail(f"the second identical train child added {added} "
                 f"compile-cache entries to {self.cache_dir}")
        return f"cache entries {before} -> {before + added}; {note}"

    # -- serving -----------------------------------------------------------

    def serve(self, args, exchange) -> str:
        """Start the server, wait for its port, let ``exchange(post, get)``
        talk to it, then SIGTERM: it must drain and exit 0."""
        cmd = [PY, "-m", "ddp_tpu.serve", *args, "--port", "0"]
        p = subprocess.Popen(cmd, cwd=ROOT, env=self.env, text=True,
                             stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        # A server that hangs must not hang the smoke: killed at the
        # deadline, it closes its pipe and fails whichever step waits.
        killer = threading.Timer(PHASE_TIMEOUT_S, p.kill)
        killer.daemon = True
        killer.start()
        lines = []
        try:
            port = None
            for line in p.stdout:       # ends at EOF if the child dies
                lines.append(line)
                m = re.search(r"serving .* on http://[^:]+:(\d+) ", line)
                if m:
                    port = int(m.group(1))
                    break
            if port is None:
                fail(f"server never announced a port (exit {p.wait()})",
                     cmd, "".join(lines))
            base = f"http://127.0.0.1:{port}"

            def post(route, body):
                req = urllib.request.Request(
                    base + route, data=json.dumps(body).encode(),
                    method="POST")
                with urllib.request.urlopen(req, timeout=120) as r:
                    return json.loads(r.read())

            def get(route):
                with urllib.request.urlopen(base + route, timeout=30) as r:
                    return json.loads(r.read())

            try:
                note = exchange(post, get)
            except OSError as e:        # urllib's errors are OSErrors
                p.kill()
                fail(f"request failed: {e!r}", cmd,
                     "".join(lines) + p.communicate()[0])
            p.send_signal(signal.SIGTERM)
            out = "".join(lines) + p.communicate(timeout=120)[0]
        finally:
            killer.cancel()
            if p.poll() is None:
                p.kill()
                p.wait()
        if p.returncode != 0:
            fail(f"server exit code {p.returncode} after SIGTERM", cmd, out)
        if "drained=clean" not in out:
            fail("server did not report a clean drain", cmd, out)
        self.check_device(cmd, out)
        return note

    def serve_predict(self) -> str:
        img = [[[(7 * i + 3 * j + 11 * c) % 256 for c in range(3)]
                for j in range(32)] for i in range(32)]

        def exchange(post, get):
            health = get("/healthz")
            if health.get("checkpoint_step") != STEPS:
                fail(f"/healthz checkpoint_step {health.get('checkpoint_step')}"
                     f", the trainer wrote step {STEPS}")
            rows = []
            for n in (1, 8, 3):     # three requests, two bucket programs
                ans = post("/predict", {"instances": [img] * n})
                preds, logits = ans.get("predictions"), ans.get("logits")
                if not (isinstance(preds, list) and len(preds) == n
                        and all(isinstance(v, int) and 0 <= v < 10
                                for v in preds)
                        and isinstance(logits, list) and len(logits) == n
                        and all(len(r) == 10 and all(math.isfinite(v)
                                                     for v in r)
                                for r in logits)):
                    fail(f"/predict answer to {n} instance(s) malformed: "
                         f"{json.dumps(ans)[:400]}")
                rows += logits
            # The same image through different bucket executables is the
            # small-input reference: one row against every other.
            scale = max(abs(v) for v in rows[0]) or 1.0
            worst = max(abs(a - b) for r in rows[1:]
                        for a, b in zip(r, rows[0])) / scale
            if worst > 0.02:
                fail(f"the same image's logits differ by {worst:.3g} of "
                     "their scale between batch buckets")
            return (f"3 /predict requests, 12 rows, class "
                    f"{max(range(10), key=rows[0].__getitem__)}, bucket "
                    f"agreement {worst:.1e} of scale")

        return self.serve(["--snapshot_path", self.path("train.pt"),
                           "--model", self.model], exchange)

    def serve_generate(self) -> str:
        def exchange(post, get):
            asks = [([1, 2, 3, 4], 4), (list(range(20)), 6),
                    ([1, 2, 3, 4], 4)]
            got = []
            for prompt, n_new in asks:
                ans = post("/generate", {"prompt": prompt,
                                         "max_new_tokens": n_new})
                toks = ans.get("tokens")
                if not (isinstance(toks, list) and len(toks) == n_new
                        and all(isinstance(t, int) and 0 <= t < 256
                                for t in toks)
                        and ans.get("prompt_len") == len(prompt)):
                    fail(f"/generate answer malformed: "
                         f"{json.dumps(ans)[:400]}")
                got.append(toks)
            if got[0] != got[2]:
                fail(f"greedy decode of one prompt gave {got[0]} then "
                     f"{got[2]}")
            return f"3 /generate requests, tokens {got[0]} {got[1]}"

        return self.serve(["--generate", "--model", "tinylm",
                           "--snapshot_path", self.path("lm/ckpt.npz")],
                          exchange)

    # -- the rest ----------------------------------------------------------

    def gather(self) -> str:
        cmd = [PY, "-m", "ddp_tpu.ops.gather"]
        out = self.run(cmd)
        self.check_device(cmd, out)
        kernel = "pallas" if self.platform == "tpu" else "xla"
        m = re.search(rf"^gather: ok kernel={kernel} .*$", out, re.M)
        if not m:
            fail(f"no 'gather: ok kernel={kernel}' line", cmd, out)
        return m.group(0)

    def table_check(self, cmd, tag: str, ok: str) -> str:
        """A child that prints a table of ``<tag>: ...`` lines, raises on a
        failed check of its own and ends with ``<tag>: ok <ok> ...``: the
        table is shown, the last line returned."""
        out = self.run(cmd)
        self.check_device(cmd, out)
        for line in re.findall(rf"^{tag}: .*$", out, re.M)[:-1]:
            print(f"[smoke]   {line}", flush=True)
        m = re.search(rf"^{tag}: ok {ok} .*$", out, re.M)
        if not m:
            fail(f"no '{tag}: ok {ok}' line", cmd, out)
        return m.group(0)

    def kernel_check(self, name: str) -> str:
        """``python -m ddp_tpu.ops.<name>``: a token-model kernel at the
        token cell's shape.  The child raises where the kernel is further
        from float32 than the XLA path or the mixer does not take it; its
        table (distances, milliseconds, block sweep, paths traced) is
        shown."""
        kernel = "pallas" if self.platform == "tpu" else "interpret"
        return self.table_check([PY, "-m", f"ddp_tpu.ops.{name}"], name,
                                f"kernel={kernel}")

    def moe(self) -> str:
        """The expert layer's products alone at both routing cells' shapes
        (tiny ones off the chip) with a quarter, a half and all of the
        buffer live: the child raises where the live loop's result is not
        the every-tile form's or, on the chip, a full buffer costs over 3%
        more; its table of milliseconds is shown."""
        return self.table_check([PY, "-m", "ddp_tpu.models.moe"], "moe",
                                f"platform={self.platform}")

    def sambay(self) -> str:
        """One training step of the second token model's pipeline stage,
        at a tiny width and at the published widths (8,192 tokens): the
        child raises on a wrong logits shape or a loss or parameter that
        is not finite."""
        steps = 2 if self.platform == "tpu" else 1
        return self.table_check(
            [PY, "-m", "ddp_tpu.models.sambay", SAMBAY_CONFIG], "sambay",
            f"steps={steps}")

    def lm(self) -> str:
        cmd = [PY, "-m", "ddp_tpu.train.lm", "--steps", "6",
               "--snapshot_path", self.path("lm/ckpt.npz")]
        out = self.run(cmd)
        self.check_device(cmd, out)
        losses = [float(v) for v in
                  re.findall(r"^\[lm\] step +\d+ +loss (\S+)$", out, re.M)]
        if not losses or not all(math.isfinite(v) for v in losses):
            fail(f"no finite '[lm] step' losses: {losses}", cmd, out)
        if not LN256_BAND[0] <= losses[0] <= LN256_BAND[1]:
            fail(f"first LM loss {losses[0]} outside {LN256_BAND} "
                 "(ln 256 = 5.545)", cmd, out)
        return f"loss {losses[0]:.4f} -> {losses[-1]:.4f}"


PHASES = ("gather", "attention", "ssd", "selscan", "moe", "sambay", "train",
          "train_again", "serve", "bf16", "resident", "shard_update",
          "resume", "lm", "generate")


def run_smoke(platform: str, *, model: str = "vgg", batch: int = 512,
              out: str = OUT, phases=PHASES, loss_band=LN10_BAND) -> dict:
    """Run ``phases`` in order; returns the result object of the last
    line.  Raises :class:`SmokeFailure` at the first failed check."""
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    s = Smoke(platform, model, batch, out, loss_band)
    root_before = set(os.listdir(ROOT))
    cache_before = s.cache_entries()
    table = {
        "gather": s.gather,
        "attention": lambda: s.kernel_check("attention"),
        "ssd": lambda: s.kernel_check("ssd"),
        "selscan": lambda: s.kernel_check("selscan"),
        "moe": s.moe,
        "sambay": s.sambay,
        "train": lambda: s.train_dp("train"),
        "train_again": s.train_again,
        "serve": s.serve_predict,
        "bf16": lambda: s.train("bf16", "--bf16"),
        "resident": lambda: s.train("resident", "--resident",
                                    native_augment="n/a"),
        "shard_update": lambda: s.train("shard_update", "--shard_update"),
        "resume": lambda: s.train("resume", "--resume", epochs=2,
                                  expect_epoch=1,
                                  snapshot=s.path("train.pt")),
        "lm": s.lm,
        "generate": s.serve_generate,
    }
    for name in phases:
        s.phase(name, table[name])
    stray = set(os.listdir(ROOT)) - root_before - {
        ".jax_cache", ".native_cache", "__pycache__"}
    if stray:
        fail(f"a child wrote into the repo root: {sorted(stray)}")
    cache_after = s.cache_entries()
    if cache_after == 0:
        fail(f"compile cache {s.cache_dir} is empty after {len(phases)} "
             "phase(s) that compiled")
    platform, kind, count = s.device
    print(f"\n[smoke] device: platform={platform} kind={kind!r} "
          f"count={count}")
    print(f"[smoke] compile cache {s.cache_dir}: {cache_before} entries "
          f"before, {cache_after} after")
    for name, wall, note in s.verdicts:
        print(f"[smoke] {name:<13} ok {wall:7.1f}s  {note}")
    print(f"[smoke] {len(s.verdicts)} phases ok in "
          f"{sum(w for _, w, _ in s.verdicts):.0f}s (smoke timings, not "
          "metrics)")
    return {"ok": True,
            "device": {"platform": platform, "kind": kind, "count": count}}


def main() -> int:
    # ``python chip_smoke.py [phase ...]``: the phases named, in the
    # table's order; all of them where none is.
    unknown = sorted(set(sys.argv[1:]) - set(PHASES))
    if unknown:
        print(f"unknown phase(s) {unknown}; phases: {', '.join(PHASES)}")
        return 2
    phases = tuple(p for p in PHASES if p in sys.argv[1:]) or PHASES
    try:
        result = run_smoke("tpu", phases=phases)
    except SmokeFailure as e:
        print(e, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
