"""The plain reference of the kvstore application's state: `key=value`
transactions applied in order to a dict."""

from __future__ import annotations


def replay(tx_lists) -> dict:
    state: dict = {}
    for txs in tx_lists:
        for tx in txs:
            k, v = tx.split(b"=", 1)
            state[k.decode()] = v.decode()
    return state
