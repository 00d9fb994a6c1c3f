"""Share of the device's idle time inside the program's ``replan.step``
spans in the rack cell: ``replan_idle_share.replan``'s reading."""

from same_reading import reader

read = reader("replan_idle_share.replan")
