"""Mean host part of a rack cell replan's batched solve, ``replan.solve``
less ``replan.solve_wait``: ``solve_host_ms.replan``'s reading."""

from same_reading import reader

read = reader("solve_host_ms.replan")
