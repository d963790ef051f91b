"""The dense decoder family, as the harness asks a family for it: the
weights of :mod:`bench.weights` and the costs of :mod:`bench.costs`
(where :mod:`bench.reference.dense` also finds them)."""
from bench.costs import decode_call, prefill_call  # noqa: F401
from bench.weights import program_params  # noqa: F401
