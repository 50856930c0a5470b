"""Host prep (crypto/tpu/bls.py prepare_chunk): mean host_prep_ms of the
batched device_chunk spans in the window."""

from harness import readers


def read(w):
    return readers.mean_host_prep_ms(w)
