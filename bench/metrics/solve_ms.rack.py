"""Mean wall of a rack cell replan's batched candidate solve, the program's
``replan.solve`` span: ``solve_ms.replan``'s reading."""

from same_reading import reader

read = reader("solve_ms.replan")
