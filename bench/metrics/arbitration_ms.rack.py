"""Mean wall of a rack cell replan's rollout arbitration over 210 FCFS rows,
the program's ``replan.arbitrate`` span: ``arbitration_ms.replan``'s reading."""

from same_reading import reader

read = reader("arbitration_ms.replan")
