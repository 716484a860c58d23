"""Model library: composable layers + the 10 assigned architectures."""
from .param import Init, Rules, P, values, specs, is_p
from .transformer import (decode_step, forward, init_cache, init_params,
                          prefill_slot, prefill_step,
                          reset_slot, rollback_slot, slot_state,
                          verify_slot, verify_step)
from .quantized import (BSEGConv, LayerStream, PackedLinear, SDVLinear,
                        bseg_conv_apply, default_bseg_plan,
                        default_sdv_plan, materialize, pack_conv_bseg,
                        pack_linear, pack_linear_sdv, serve_params)

__all__ = ["Init", "Rules", "P", "values", "specs", "is_p", "decode_step",
           "forward", "init_cache", "init_params", "prefill_slot", "prefill_step",
           "reset_slot", "rollback_slot", "slot_state", "verify_slot",
           "verify_step", "BSEGConv", "LayerStream",
           "PackedLinear", "SDVLinear", "bseg_conv_apply",
           "default_bseg_plan", "default_sdv_plan", "materialize",
           "pack_conv_bseg", "pack_linear", "pack_linear_sdv",
           "serve_params"]
