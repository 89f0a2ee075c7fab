"""Traffic kinds: the general code that the traffic files parameterize."""
