"""Mean solver iterations of the deployed candidate per rack cell replan
(both loops of the rack solve): ``solver_iters.replan``'s reading."""

from same_reading import reader

read = reader("solver_iters.replan")
