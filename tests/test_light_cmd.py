"""`cometbft_tpu light --sequential` (reference cmd/cometbft/commands/
light.go `--sequential`, light.SequentialVerification()): the command
hands the flag to the client's constructor, so the proxy it serves
verifies every header between the trusted height and the one asked for;
without it, by bisection, it stores only the two ends.

In `test_light_proxy.py`'s manner: an in-process cluster commits real
blocks, node 0's stores are served over JSON-RPC, and the command is run
against that, its proxy asked for the tip."""

import threading
import time

import pytest

from cluster import Cluster
from cometbft_tpu.cmd import main as cmd_main
from cometbft_tpu.light.client import LightClient
from cometbft_tpu.light.rpc import LightProxy
from cometbft_tpu.rpc.client import RPCClient
from cometbft_tpu.rpc.server import RPCEnvironment, RPCServer

CHAIN = "light-cmd-chain"


@pytest.fixture(scope="module")
def net():
    c = Cluster(4, chain_id=CHAIN)
    srv = None
    try:
        c.start()
        deadline = time.monotonic() + 120
        while c.nodes[0].cs.state.last_block_height < 6:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        c.stop()
        node = c.nodes[0]
        srv = RPCServer(RPCEnvironment(
            chain_id=CHAIN, block_store=node.block_store,
            state_store=node.state_store, app_query=node.app,
            state_getter=lambda: node.cs.state))
        srv.start()
        yield node, srv.addr[1]
    finally:
        if srv is not None:
            srv.stop()
        c.stop()


def _run_light(monkeypatch, node, port, *flags):
    """Run the command until its proxy has answered for the tip, then
    send it its ^C; returns the tip and the command's exit code."""
    started, done = threading.Event(), threading.Event()
    proxies, codes = [], []
    real_start, real_sleep = LightProxy.start, time.sleep

    def start(self):
        real_start(self)
        proxies.append(self)
        started.set()

    def sleep(seconds):
        # the command's own wait for ^C, and nobody else's sleep
        if seconds != 3600:
            return real_sleep(seconds)
        done.wait(60)
        raise KeyboardInterrupt

    monkeypatch.setattr(LightProxy, "start", start)
    monkeypatch.setattr(time, "sleep", sleep)
    trusted = node.block_store.load_block_meta(1)[0].hash
    argv = ["light", CHAIN, "--primary", f"127.0.0.1:{port}",
            "--trusted-height", "1", "--trusted-hash", trusted.hex(), *flags]
    thread = threading.Thread(
        target=lambda: codes.append(cmd_main.main(argv)), daemon=True)
    thread.start()
    try:
        assert started.wait(60)
        tip = node.block_store.height() - 1
        served = RPCClient("127.0.0.1", proxies[0].addr[1]).header(tip)
        assert int(served["header"]["height"]) == tip
    finally:
        done.set()
        thread.join(60)
    return tip, codes


def _trusted_heights(light, tip):
    return [h for h in range(1, tip + 1)
            if light.trusted_light_block(h) is not None]


@pytest.mark.parametrize("flags, sequential", [((), False),
                                               (("--sequential",), True)])
def test_light_command_hands_sequential_to_the_client(monkeypatch, net,
                                                      flags, sequential):
    node, port = net
    seen = []
    init = LightClient.__init__

    def recording(self, *a, **kw):
        init(self, *a, **kw)
        seen.append(self)
    monkeypatch.setattr(LightClient, "__init__", recording)
    tip, codes = _run_light(monkeypatch, node, port, *flags)
    assert codes == [0] and len(seen) == 1
    assert seen[0].sequential is sequential
    want = list(range(1, tip + 1)) if sequential else [1, tip]
    assert _trusted_heights(seen[0], tip) == want


def test_the_flag_is_off_by_default():
    args = cmd_main.build_parser().parse_args(
        ["light", CHAIN, "--primary", "127.0.0.1:1"])
    assert args.sequential is False
