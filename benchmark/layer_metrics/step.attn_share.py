"""Share of the device's busy time in attention, all passes: the scopes
``zoo_attn.*`` (the q/k/v products, rotary, q/k norm, the attention itself,
the output product), ``zoo_mla.*`` (latent attention's six) and the flash
kernels by their own name, each event once (``lib/step_ledger.py``). In the
latent-attention cell it reads what ``attn.latent_time_share`` reads."""

from benchmark.lib import step_ledger

FLASH_KERNELS = ("zoo_flash",)


def read(view):
    return step_ledger.share(
        view, lambda led: step_ledger.seconds(led, ("zoo_attn.", "zoo_mla."),
                                              FLASH_KERNELS))
