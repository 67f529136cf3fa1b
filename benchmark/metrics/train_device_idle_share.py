"""Device: ``device_idle_share`` as read in the training cells, where it
moves ``train_tokens_per_s``."""
import readers

reduce = readers.same_as("device_idle_share")
