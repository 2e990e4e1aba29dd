"""gat_attention_roofline (%): the ``gat_attention`` kernel's share of its
roofline (``kernel_share.share``): the least time its round of work could
take on the chip, over the device time its operations took."""
from kernel_share import share


def read(m):
    return share(m, "gat_attention")
