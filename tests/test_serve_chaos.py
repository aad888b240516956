"""Chaos drill: SIGTERM the live serving process mid-stream.

ISSUE-3 satellite (tests/test_resilience.py conventions, marker ``chaos``):
requests admitted before the signal complete with real 200 answers, requests
arriving after it get clean 503s (never hangs, never connection-reset while
the drain runs), and the process exits 0 — the supervisor-friendly drain
contract of serve/server.py, exercised through the real CLI entry point on
the CPU mesh.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from helpers import write_vocab

pytestmark = pytest.mark.chaos

REPO_ROOT = str(Path(__file__).resolve().parents[1])

_QUESTION = "what is the capital of england ?"
_DOCUMENT = (
    "<P> London is the capital of England . </P> "
    "<P> Big Ben was built in the city . </P>"
)


def _admitted_requests(url) -> int:
    """qa_requests_total from the live /metrics page (0 if unreadable)."""
    try:
        with urllib.request.urlopen(f"{url}/metrics", timeout=5) as resp:
            text = resp.read().decode("utf-8")
    except (urllib.error.URLError, ConnectionError, OSError):
        return 0
    for line in text.splitlines():
        if line.startswith("qa_requests_total"):
            return int(float(line.split()[-1]))
    return 0


def _post(url, timeout=60.0):
    req = urllib.request.Request(
        f"{url}/v1/qa",
        data=json.dumps(
            {"question": _QUESTION, "document": _DOCUMENT}
        ).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def test_serve_sigterm_drains_inflight_and_503s_late_arrivals(tmp_path):
    vocab = write_vocab(tmp_path)
    ready = tmp_path / "ready.json"
    log_path = tmp_path / "serve.log"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "ml_recipe_tpu.cli.serve",
            "--model", "bert-tiny",
            "--vocab_file", str(vocab),
            "--lowercase",
            "--buckets", "8x64",
            # long coalescing deadline: the first wave is still QUEUED when
            # SIGTERM lands, so the drill proves queued-but-admitted work is
            # flushed to real answers, not dropped
            "--max_batch_delay_ms", "600",
            "--max_question_len", "16",
            "--doc_stride", "24",
            "--port", "0",
            "--ready_file", str(ready),
            "--hbm_preflight", "false",
        ],
        env=env, cwd=REPO_ROOT,
        # a file, not a pipe: nobody reads while the server runs, and a
        # child that fills an unread pipe (the params banner plus one loader
        # line per compile-cache hit is enough) blocks before it is ready
        stdout=log, stderr=subprocess.STDOUT,
    )
    log.close()  # the child holds its own descriptor
    try:
        deadline = time.monotonic() + 600
        while not ready.exists():
            assert proc.poll() is None, (
                f"serve exited rc={proc.returncode} before ready:\n"
                f"{log_path.read_text()[-4000:]}"
            )
            assert time.monotonic() < deadline, "server never became ready"
            time.sleep(0.2)
        info = json.loads(ready.read_text())
        url = f"http://{info['host']}:{info['port']}"

        # first wave: admitted before the signal, must all complete
        first = [None] * 4

        def worker(i):
            first[i] = _post(url)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        # barrier on ADMISSION, not wall-clock: qa_requests_total increments
        # the moment a request is admitted (still queued — the 600 ms
        # coalescing deadline is open), so once the counter reads 4 the
        # whole first wave is provably inside the drain guarantee. A plain
        # sleep raced the workers: any not yet admitted got the late-arrival
        # 503 instead and the 200-assertion below flaked.
        admit_deadline = time.monotonic() + 60
        while _admitted_requests(url) < 4:
            assert time.monotonic() < admit_deadline, (
                "first wave never fully admitted"
            )
            time.sleep(0.02)
        proc.send_signal(signal.SIGTERM)

        # second barrier, on the DRAIN FLAG: the admission gate flips in
        # the child's signal handler, asynchronously to send_signal — a
        # POST racing ahead of the flip is legitimately admitted and then
        # blocks until the 600 ms batch deadline flushes it, eating the
        # whole drain window from this side of the socket. The first wave
        # is still queued behind that open deadline, so the listener is
        # provably up while we wait for /healthz to report draining.
        while True:
            try:
                with urllib.request.urlopen(f"{url}/healthz", timeout=5) as r:
                    if json.loads(r.read()).get("status") == "draining":
                        break
            except (urllib.error.URLError, ConnectionError, OSError):
                pytest.fail("listener closed before draining was observable")
            time.sleep(0.01)

        # late arrivals: keep posting through the drain window; clean 503s
        # until the listener closes (connection errors only AFTER that)
        late = []
        t_end = time.monotonic() + 15
        while time.monotonic() < t_end:
            try:
                status, _ = _post(url, timeout=5)
                late.append(status)
            except (urllib.error.URLError, ConnectionError, OSError):
                break
            time.sleep(0.02)

        for t in threads:
            t.join(timeout=120)
        rc = proc.wait(timeout=120)

        assert rc == 0, log_path.read_text()[-4000:]
        for status, body in first:
            assert status == 200, (status, body)
            assert body["label"], body
        assert 503 in late, (
            f"no clean 503 observed during the drain window: {late}"
        )
        # once draining began nothing was ever admitted again
        tail = late[late.index(503):]
        assert set(tail) == {503}, late
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
