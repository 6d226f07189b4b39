"""Traffic: mixes (.json data) and the generators that drive each kind."""
