"""Operations and bytes of each kernel the timed path runs, computed from
its shapes, and how to find its events in a device trace."""
