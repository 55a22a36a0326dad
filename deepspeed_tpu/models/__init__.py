from .transformer import (TransformerConfig, TransformerLM,  # noqa: F401
                          afmoe_config, build_model, glm_moe_dsa_config,
                          gpt2_config,
                          granite_hybrid_config, kimi_linear_config,
                          longcat_flash_config, neox_config,
                          openpangu_ultra_moe_config, phi4_flash_config,
                          sdar_moe_config, zaya_config)
