"""The deployment class the serve cells deploy: ``LLMServer`` with the
benchmark's tokenizer, registered so that the correctness check can reach
the model and its weights.

At MODULE level, in a module of its own, on purpose. Serve's controller
checkpoints every deployment with ``cloudpickle``; a class defined inside
a function is pickled BY VALUE, together with the globals its methods
name, and ``SERVERS`` holds the live server: until PR 30 every serve run
copied all weights and the whole KV pool to the host and pickled them
(24 of ``setup_s``'s ~48 s, ~3 bytes of host memory for every byte on the
device: 29 of the machine's 40 GiB in ``batch_decode`` at ``max_seq``
3072, past 40 at 8192). A class that can be imported is pickled as its
name. Imported only once a backend is up (it imports the program).
"""

from __future__ import annotations

from ray_tpu.llm.serving import LLMServer

SERVERS: list = []            # the in-process replicas' servers


class IdTokenizer:
    """Token ids in, token ids out: the benchmark sends prompts as ids
    and reads ``token_id`` from each chunk. Not a ``ByteTokenizer``, so
    ``LLMServer._parse`` sets NO stop token: with random weights a stop
    id (the byte tokenizer's 257) would end streams at random, and a
    window would not hold the same work in every run."""

    def encode(self, text, add_bos: bool = True):
        raise TypeError("the benchmark sends token ids, not text")

    def decode(self, ids) -> str:
        return ""


class BenchLLMServer(LLMServer):
    def __init__(self, config):
        super().__init__(config)
        self.tokenizer = IdTokenizer()
        SERVERS.append(self)
