from .ops import (cws_sketch, cws_sketch_batch, decode_attention_pallas,
                  flash_decode_attention, fused_selective_scan,
                  icws_hash_grid, icws_sketch, icws_sketch_batch,
                  icws_token_params, minhash_sketch, multiset_sketch,
                  selective_scan_pallas)

__all__ = ["cws_sketch", "cws_sketch_batch", "multiset_sketch",
           "flash_decode_attention", "fused_selective_scan",
           "icws_token_params", "icws_hash_grid", "icws_sketch",
           "icws_sketch_batch", "minhash_sketch", "decode_attention_pallas",
           "selective_scan_pallas"]
