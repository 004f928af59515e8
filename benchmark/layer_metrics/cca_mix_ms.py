"""Device time of one step in what compressed convolutional attention does to
queries, keys and values between their projections and the kernel, forward and
backward, all layers: operations under the model's ``cca.mix`` scope
(``models/transformer.py`` ``ConvolutionalAttention``): the depthwise 2-tap
convolution, the 2-tap convolution within a head (the one product in it), the
q-k mean, the L2 norm with the key temperature, the rotary turn of half a
head, the value shift and the transposes to ``[B, H, S, D]``. All but the one
product is elementwise over ``[S, 1,024]`` and ``[S, 256]``: bound by memory,
so the number says how much of it XLA fuses."""

from reduce import named_scopes


def read(ctx):
    return named_scopes.per_step_ms(ctx, "cca.mix")
