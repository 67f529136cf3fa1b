"""Device: ``hbm_peak_gb`` as read in the training cells, where it moves
``train_tokens_per_s``."""
import readers

reduce = readers.same_as("hbm_peak_gb")
