"""Mean trip count of the rack cell's batched solve per replan, the program's
``solver.trips`` counter: ``solver_trips.replan``'s reading."""

from same_reading import reader

read = reader("solver_trips.replan")
