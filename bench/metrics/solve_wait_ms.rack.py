"""Mean time the host waits on the device for a rack cell replan's batched
solve, the program's ``replan.solve_wait`` span: ``solve_wait_ms.replan``'s reading."""

from same_reading import reader

read = reader("solve_wait_ms.replan")
