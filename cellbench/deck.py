"""The one general traffic generator: a traffic file's parameters and a
seed in, requests out.

A *deck* is a fixed multiset of requests: `blocks` blocks, each holding
one prompt of every length in `prompt_lengths`, paired with the output
lengths by a Latin square so that every block also holds every output
length once and every (prompt, output) pair occurs equally often in a
deck. The seed permutes the order inside each block and the order of
the blocks, and draws the token ids; it never changes the multiset, so
every seed offers the same work and any stretch of the stream is
balanced to within one block. No two neighbours in the stream share a
prompt length (the program compiles its cache merge per number of
same-length prompts admitted together; see PERF.md).
"""
import numpy as np


def rng_for(seed, *stream):
    """A numpy generator for one named stream of one run. `seed` is any
    whole number (the driver's are above 2**31)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  int(seed) >> 32, *map(int, stream)])


def deck_shapes(traffic, seed, deck_index):
    """(prompt_len, output_len) for each request of deck `deck_index`,
    block by block. Neighbours across blocks are settled by `Stream`."""
    plens = list(traffic["prompt_lengths"])
    olens = list(traffic["output_lengths"])
    n = len(plens)
    if len(olens) != n:
        raise ValueError("a deck pairs as many output lengths as prompt "
                         "lengths (a Latin square)")
    blocks = int(traffic["blocks"])
    if blocks % n:
        raise ValueError("blocks must be a multiple of %d so that every "
                         "pair occurs equally often" % n)
    rng = rng_for(seed, 1, deck_index)
    order = rng.permutation(blocks)
    out = []
    for b in order:
        block = [(plens[i], olens[(i + b) % n]) for i in range(n)]
        perm = rng.permutation(n)
        out.append([block[i] for i in perm])
    return out


class Stream:
    """The request stream, made a deck at a time as it is drawn from,
    so it cannot run dry however fast the program gets. Request i is
    a dict with `prompt` (int64 ids) and `max_new`; `shapes` holds the
    (prompt_len, output_len) blocks made so far. No two neighbouring
    requests share a prompt length: where a block would start with
    the length the previous one ended on, its first two entries swap
    (a block holds each length once, so the swap settles it)."""

    def __init__(self, traffic, seed, vocab):
        self._traffic, self._seed, self._vocab = traffic, seed, vocab
        self._ids = rng_for(seed, 2)
        self._decks = 0
        self.shapes, self._reqs = [], []

    def _another_deck(self):
        for block in deck_shapes(self._traffic, self._seed, self._decks):
            if self.shapes and len(block) > 1 and \
                    block[0][0] == self.shapes[-1][-1][0]:
                block[0], block[1] = block[1], block[0]
            self.shapes.append(block)
            for plen, olen in block:
                self._reqs.append({
                    "prompt": self._ids.integers(0, self._vocab, plen,
                                                 dtype=np.int64),
                    "max_new": int(olen)})
        self._decks += 1

    def __getitem__(self, i):
        while i >= len(self._reqs):
            self._another_deck()
        return self._reqs[i]


def stream(traffic, seed, n_decks):
    """Blocks of (prompt_len, output_len) for the first `n_decks`
    decks of the stream."""
    per_deck = int(traffic["blocks"]) * len(traffic["prompt_lengths"])
    s = Stream(traffic, seed, 2)
    s[n_decks * per_deck - 1]
    return s.shapes


def requests(traffic, seed, n_decks, vocab):
    """The first `n_decks` decks of the request stream, as a list."""
    per_deck = int(traffic["blocks"]) * len(traffic["prompt_lengths"])
    s = Stream(traffic, seed, vocab)
    return [s[i] for i in range(n_decks * per_deck)]
