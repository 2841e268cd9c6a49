"""Faults planted under the timed path, to see ``correct`` come out false.

Each ``plant_<name>(setattr)`` patches the program with the given
``setattr`` (``monkeypatch.setattr`` in the tests, the builtin in
``bench/calibrate.py``, whose process ends with the readings):

* ``altered_token``: every decode step's first slot gets the next token id;
* ``state_unchanged``: a decode step's cache writes are dropped (the pool
  handed back is the one the step started from);
* ``vote_off``: tier 0 answers whatever its members say (theta -1).
"""
from __future__ import annotations


def plant_altered_token(setattr):
    from repro.serve.slot_stream import TierBackend

    orig = TierBackend.decode

    def decode(self, tok, pos):
        out = orig(self, tok, pos).copy()
        out[:, 0] = (out[:, 0] + 1) % self.tier.cfg.vocab_size
        return out

    setattr(TierBackend, "decode", decode)


def plant_state_unchanged(setattr):
    from repro.serve.slot_stream import TierBackend

    orig = TierBackend.decode

    def decode(self, tok, pos):
        pool = self.pool_dev
        out = orig(self, tok, pos)
        self.pool_dev = pool
        return out

    setattr(TierBackend, "decode", decode)


def plant_vote_off(setattr):
    from repro.serve import cascade_server

    setattr(cascade_server._CascadeRun, "effective_theta", lambda self, i: -1.0)


FAULTS = {
    "altered_token": plant_altered_token,
    "state_unchanged": plant_state_unchanged,
    "vote_off": plant_vote_off,
}
