"""Mean host wall of the rack cell's closed loop per segment, ``loop.simulate``
plus ``loop.observe``: ``loop_ms.replan``'s reading."""

from same_reading import reader

read = reader("loop_ms.replan")
