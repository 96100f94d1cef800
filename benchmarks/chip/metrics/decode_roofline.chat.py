"""decode_roofline.chat: the least bytes of each traced decode step (the
weights once, plus K and V of the live positions of the active slots, as
the tick left them) over 819 GB/s, against the device time of the slot
decode programs.  Not the whole t_max lanes the jnp path reads today, so
a paged kernel cannot read past 100%.  Programs are matched by jit name."""
from readers import decode_roofline_pct

PROGRAMS = ("slot_decode",)


def read(run):
    return decode_roofline_pct(run, PROGRAMS)
