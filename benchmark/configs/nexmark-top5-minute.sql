-- Upstream Arroyo's first pipeline (the docs' tutorial and the README): the
-- top five NEXmark auctions by number of bids over a 60 s window sliding by
-- 2 s, ROW_NUMBER() OVER (PARTITION BY window ORDER BY count DESC) <= 5,
-- read from the nexmark connector. "bid" stands for `bid IS NOT NULL` in
-- this engine's flattened schema; ties at the fifth place go to the lower
-- auction id (the tutorial leaves them to the engine). The dollar names are
-- filled from the configuration's generator settings, the traffic mix's
-- rate and --seed.
CREATE TABLE nexmark (
  "bid" BOOLEAN, "bid.auction" BIGINT
) WITH (
  connector = 'nexmark',
  inter_event_micros = $inter_event_micros,
  first_event_micros = $first_event_micros,
  event_rate = $event_rate,
  seed = $seed
);
CREATE TABLE top_auctions (
  auction BIGINT, num BIGINT, row_num BIGINT, ws TIMESTAMP
) WITH (connector = '$sink', type = 'sink');
INSERT INTO top_auctions
SELECT auction, num, row_num, window_start FROM (
  SELECT *, ROW_NUMBER() OVER (
    PARTITION BY window ORDER BY num DESC, auction ASC) AS row_num
  FROM (
    SELECT "bid.auction" AS auction, count(*) AS num,
      hop(interval '2 seconds', interval '60 seconds') AS window
    FROM nexmark WHERE "bid" GROUP BY "bid.auction", window
  )
) WHERE row_num <= 5;
