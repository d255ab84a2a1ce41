"""Communication-compression subsystem: the GradientCodec registry
(``none`` / ``int8`` / ``sign1bit`` / ``topk`` + ``register_codec``), the
per-client error-feedback state, and the uplink byte accounting — the
fourth plugin registry next to algorithms / executors / engines."""
from repro.comm.codecs import (GradientCodec, available_codecs, get_codec,
                               register_codec, resolve_codec)
from repro.comm.transport import (client_coded_accumulate,
                                  comm_bytes_per_client, init_comm_state)

__all__ = ["GradientCodec", "register_codec", "get_codec",
           "available_codecs", "resolve_codec", "init_comm_state",
           "comm_bytes_per_client", "client_coded_accumulate"]
