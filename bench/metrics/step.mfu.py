"""Model step: model FLOP utilisation of the whole step.  The operations
the model needs for the tokens prefilled and decoded in the window
(``bench.work``: matrix products, causal attention over the live context,
the logits of decoded tokens and of each prompt's last position), over
the window times the chips times the chip's bf16 peak."""


def read(rec):
    w = rec.window
    if not w.model_flops or not w.seconds:
        return None
    return 100.0 * w.model_flops / (w.seconds * rec.chips *
                                    rec.peaks["bf16_flops"])
