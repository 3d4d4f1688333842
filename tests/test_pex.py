"""PEX: address book persistence and gossip-driven mesh formation
(reference p2p/pex/pex_reactor_test.go, addrbook_test.go)."""

import pytest

# the real TCP stack rides SecretConnection (X25519/ChaCha20);
# containers without the cryptography wheel skip these — the
# in-process cluster and simnet suites cover the same protocol
# logic over crypto-free transports
pytest.importorskip("cryptography")


import time

from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.p2p.pex import AddressBook, PexReactor
from cometbft_tpu.p2p.switch import Switch


def test_address_book_persistence(tmp_path):
    path = str(tmp_path / "addrbook.json")
    book = AddressBook(path)
    book.add("id1", "127.0.0.1", 1111)
    book.add("id2", "127.0.0.1", 2222)
    book.remove("id1")
    book2 = AddressBook(path)
    assert len(book2) == 1
    assert book2.entries() == [("id2", "127.0.0.1", 2222)]


def _node(name):
    sw = Switch(Ed25519PrivKey.generate(), "pex-net", name)
    pex = PexReactor(AddressBook(), ensure_interval_s=0.2)
    pex.attach(sw)
    sw.add_reactor(pex)
    sw.listen()
    pex.start()
    return sw, pex


def test_pex_discovers_full_mesh():
    """Three nodes, one seed link each: PEX spreads addresses until all
    three interconnect without explicit dials."""
    nodes = [_node(f"n{i}") for i in range(3)]
    try:
        # n1 and n2 each know only n0 (the seed topology)
        h0, p0 = nodes[0][0].transport.node_info.listen_addr.split(":")
        nodes[1][0].dial(h0, int(p0))
        nodes[2][0].dial(h0, int(p0))
        # both conditions under ONE deadline: a peer can be connected a
        # moment before its address reaches the book
        def meshed():
            return all(len(sw.peers()) >= 2 and len(pex.book) >= 2
                       for sw, pex in nodes)

        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not meshed():
            time.sleep(0.05)
        assert all(len(sw.peers()) >= 2 for sw, _ in nodes), \
            [(sw._moniker, [p.id[:8] for p in sw.peers()])
             for sw, _ in nodes]
        # address books learned all peers
        assert all(len(pex.book) >= 2 for _, pex in nodes), \
            [(sw._moniker, len(pex.book)) for sw, pex in nodes]
    finally:
        for sw, pex in nodes:
            pex.stop()
            sw.stop()
