"""A configuration's own reference module, as the tests name one: the
dense reference, counting its calls, with a FLOP count of its own that
reads twice the dense one, so a test can tell which module was used."""
from collections import Counter

import flops
import reference

CALLS: Counter = Counter()


class Reference(reference.Reference):

    def __init__(self, m, seed, quant=None):
        super().__init__(m, seed, quant)
        CALLS["init"] += 1

    def logits(self, seqs):
        CALLS["logits"] += 1
        return super().logits(seqs)

    def embed(self, seqs):
        CALLS["embed"] += 1
        return super().embed(seqs)

    def rerank(self, seqs, head_token):
        CALLS["rerank"] += 1
        return super().rerank(seqs, head_token)


def decode_flops(m, tokens):
    CALLS["decode_flops"] += 1
    return 2 * flops.decode_flops(m, tokens)
