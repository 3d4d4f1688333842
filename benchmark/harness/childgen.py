"""Traffic generation in a child process held to the CPU backend.

The parent owns the chip and spends set-up tracing the kernels under the
interpreter lock; the child makes the traffic meanwhile on another core.
The child's environment says JAX_PLATFORMS=cpu, so whatever JAX it meets
never asks for the chip. It writes one pickle to its standard output,
which only the parent reads (bytes this benchmark wrote itself)."""

from __future__ import annotations

import json
import os
import pickle
import subprocess
import sys
import threading


def generate_here(manifest, generator: str, params: dict):
    return manifest.load_module("generators", generator).make(params)


class ChildGenerator:
    def __init__(self, manifest, generator: str, params: dict):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("COMETBFT_TPU_DEVICE_SERVER", None)
        self._proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), manifest.repo_root,
             generator, json.dumps(params)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            cwd=manifest.repo_root)
        self._out = self._err = b""
        # drain both pipes while the parent works: a full pipe would
        # stall the child until result() is called
        self._thread = threading.Thread(target=self._drain, daemon=True)
        self._thread.start()

    def _drain(self):
        self._out, self._err = self._proc.communicate()

    def result(self, timeout: float = 900.0):
        self._thread.join(timeout)
        if self._thread.is_alive():
            self.close()
            raise TimeoutError("traffic generator child did not finish")
        if self._proc.returncode != 0:
            raise RuntimeError(
                f"traffic generator child exited {self._proc.returncode}: "
                f"{self._err.decode(errors='replace')[-2000:]}")
        return pickle.loads(self._out)

    def close(self):
        if self._proc.poll() is None:
            self._proc.kill()
        self._proc.wait()
        self._thread.join(5.0)


def _child_main(argv) -> int:
    repo_root, generator, params = argv[1], argv[2], json.loads(argv[3])
    # the harness and the program live in THIS file's checkout; the
    # manifest's root may be another directory (tests)
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness.manifest import Manifest
    payload = generate_here(Manifest(repo_root), generator, params)
    out = sys.stdout.buffer
    out.write(pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL))
    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(_child_main(sys.argv))
