"""``tokens_per_s`` times the model operations of a token (mean of
``work.model_ops_per_token`` at each emitted token's context) over the
chip's int8 peak, in percent."""
import readers


def read(r):
    return readers.decode_mfu(r)
