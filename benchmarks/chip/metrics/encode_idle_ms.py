"""Device-idle milliseconds per traced round while the host encodes or
decodes uploads (``CommState.encode_upload``/``roundtrip``/``decode_upload``):
the self time of the program's ``phase.uplink`` and ``phase.uplink_decode``
spans (``spans.py``)."""
import spans


def read(ctx):
    return spans.layer_idle_ms(ctx, "encode")
