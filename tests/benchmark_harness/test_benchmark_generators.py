"""Both generators at 4 blocks x 8 validators: distinct signatures, the
same seed gives the same bytes, another seed gives others, and nothing
reaches the program's verified-signature cache; and every generator that
a cell's traffic file names, at the cell's tiny sizes: no memo of the
program rides its payload."""

import pickle

import pytest

from conftest import CELLS, REPO
from benchmark.harness.manifest import Manifest

CFG = {"validators": 8, "voting_power": 10, "txs_per_block": 2,
       "tile_size": 4}
CHAIN_MIX = dict(blocks_per_window_second=4, warmup_blocks=4,
                 probe_blocks=4, probe_bad_height=2, probe_bad_index=1)
COMMIT_MIX = dict(commits_per_window_second=4, warmup_commits=1,
                  probe_commits=2)


def _chain_sigs(payload):
    return [cs.signature for b in payload["main"]["blocks"]
            for cs in b.last_commit.signatures]


def _stream_sigs(payload):
    return [s for row in payload["stream"] for s in row["sigs"]]


@pytest.mark.parametrize("generator, mix, sigs_of, n_sigs", [
    ("fresh_chain", CHAIN_MIX, _chain_sigs, 4 * 8),
    ("commit_stream", COMMIT_MIX, _stream_sigs, 4 * 8),
])
def test_generator_is_seeded_and_distinct(generator, mix, sigs_of, n_sigs,
                                          fresh_sigcache):
    from cometbft_tpu.pipeline.cache import shared_cache
    make = Manifest(REPO).load_module("generators", generator).make
    big = 2**31 + 12345

    def run(seed):
        return sigs_of(make({"seed": seed, "seconds": 1.0, "config": CFG,
                             "traffic": mix}))

    a, b, c = run(big), run(big), run(big + 1)
    assert len(a) == n_sigs and len(set(a)) == n_sigs
    assert a == b
    assert not set(a) & set(c)
    assert len(shared_cache()) == 0


def _memos(payload) -> list:
    """`path.name` of every attribute reachable from `payload` that is
    not None and whose name ends in `_memo` or `_template`: what the
    program keeps on an instance so as not to compute it twice."""
    found, seen, stack = [], set(), [("payload", payload)]
    while stack:
        path, obj = stack.pop()
        if id(obj) in seen or isinstance(obj, (bytes, str, int, float,
                                               bool, type(None))):
            continue
        seen.add(id(obj))
        if isinstance(obj, dict):
            stack += [(f"{path}[{k!r}]", v) for k, v in obj.items()]
        elif isinstance(obj, (list, tuple, set, frozenset)):
            stack += [(f"{path}[{i}]", v) for i, v in enumerate(obj)]
        else:
            attrs = dict(getattr(obj, "__dict__", {}))
            for cls in type(obj).__mro__:
                for name in getattr(cls, "__slots__", ()):
                    if hasattr(obj, name):
                        attrs[name] = getattr(obj, name)
            for name, value in attrs.items():
                if name.endswith(("_memo", "_template")) \
                        and value is not None:
                    found.append(f"{path}.{name}")
                stack.append((f"{path}.{name}", value))
    return found


@pytest.mark.parametrize("cell_name", CELLS)
def test_no_memo_of_the_program_rides_a_payload(cell_name, tiny_root,
                                                fresh_sigcache):
    """Four of the last six gains before PR 33 were memoisations. A memo
    that a generator's own calls set, and that comes through the pickle
    from its child, is a cost that the node under test never pays and
    every joining node does: the payload has to arrive without any."""
    manifest = Manifest(tiny_root)
    cell = manifest.cell(cell_name)
    make = manifest.load_module("generators",
                                cell.traffic["generator"]).make
    made = make({"seed": 2**31 + 330, "seconds": 2.0, "config": cell.config,
                 "traffic": cell.traffic})
    payload = pickle.loads(pickle.dumps(made,
                                        protocol=pickle.HIGHEST_PROTOCOL))
    assert _memos(payload) == []
    # the walk does find one where there is one: the generator's own
    # objects, before the pickle, still hold what the program's
    # `__getstate__` / `__reduce__` keep out of it
    blocks = [b for chain in made.values() if isinstance(chain, dict)
              for b in chain.get("blocks", [])]
    if blocks:
        blocks[0].header.hash()
        assert "payload.header._hash_memo" in _memos(blocks[0])


def test_window_length_is_whole_tiles():
    gen = Manifest(REPO).load_module("generators", "fresh_chain")
    assert gen.window_blocks(15, 55, 16) == 832
    assert gen.window_blocks(0.1, 1, 16) == 16


def test_reference_sign_bytes_equal_the_programs():
    from benchmark.reference import canonical_vote
    from cometbft_tpu.types.block import BlockID, PartSetHeader
    from cometbft_tpu.types.proto import Timestamp
    from cometbft_tpu.types.vote import PRECOMMIT_TYPE, Vote
    for height, nanos, total in ((1, 0, 1), (300, 199, 3), (2**40, 7, 1)):
        bid = BlockID(b"\x11" * 32, PartSetHeader(total, b"\x22" * 32))
        vote = Vote(type_=PRECOMMIT_TYPE, height=height, round=0,
                    block_id=bid, timestamp=Timestamp(1_700_000_000, nanos),
                    validator_address=b"\x33" * 20, validator_index=0)
        assert vote.sign_bytes("bench-1") == \
            canonical_vote.precommit_sign_bytes(
                "bench-1", height, 0, bid.hash, total, bid.parts.hash,
                1_700_000_000, nanos)
